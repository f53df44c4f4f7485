"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode
on CPU; TPU is the compile target).

Since the hot-path PR, ``ops`` routes to compiled jnp fallbacks off-TPU
(``kernel_mode() == "auto"``); the property tests below pin the mode per
path so the Pallas interpret source keeps its coverage, and assert the
two paths agree on arbitrary ragged/1-sample shapes.  Deterministic
(no-hypothesis) parity coverage lives in tests/test_hotpath.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
hypothesis = pytest.importorskip("hypothesis")  # optional dev dep
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ops, ref


@pytest.mark.parametrize("C", [2, 4, 8, 16, 32])
@pytest.mark.parametrize("L", [1024, 4096, 333])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tree_aggregate_sweep(C, L, dtype):
    key = jax.random.key(C * L)
    g = jax.random.normal(key, (C, L), dtype)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (C,))
    np.testing.assert_allclose(
        np.asarray(ops.tree_aggregate(g, w)),
        np.asarray(ref.tree_aggregate_ref(g, w)),
        rtol=1e-5, atol=1e-5,
    )


def test_tree_aggregate_pytree_matches_fedavg():
    from repro.fl.aggregation import fedavg

    key = jax.random.key(0)
    updates = [
        {"a": jax.random.normal(jax.random.fold_in(key, i), (40, 7)),
         "b": jax.random.normal(jax.random.fold_in(key, 10 + i), (13,))}
        for i in range(5)
    ]
    w = [1.0, 2.0, 3.0, 0.5, 1.5]
    agg = ops.tree_aggregate_pytree(updates, np.asarray(w) / np.sum(w))
    expect = fedavg(updates, w)
    for a, b in zip(jax.tree.leaves(agg), jax.tree.leaves(expect)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("R", [64, 256, 777])
def test_quantize_bit_exact_and_bounded(R):
    key = jax.random.key(R)
    x = jax.random.normal(key, (R, 256)) * 5
    rnd = jax.random.uniform(jax.random.fold_in(key, 1), (R, 256))
    q, s = ops.qsgd_quantize(x, rnd)
    qr, sr = ref.quantize_ref(x, rnd)
    assert bool(jnp.all(q == qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    # dequant error bounded by one quantization step per element
    deq = ops.qsgd_dequantize(q, s)
    assert bool(jnp.all(jnp.abs(deq - x) <= s + 1e-6))


@pytest.mark.parametrize("R", [1, 255, 257, 779])
def test_quantize_kernel_takes_ragged_rows(R):
    """The Pallas kernel (interpret) at a row count off its block size:
    equal to the reference, and bit for bit to the block-padded call."""
    from repro.kernels import quantize

    key = jax.random.key(R)
    x = jax.random.normal(key, (R, 256)) * 5
    rnd = jax.random.uniform(jax.random.fold_in(key, 1), (R, 256))
    q, s = quantize.qsgd_quantize(x, rnd, interpret=True)
    assert q.shape == (R, 256) and s.shape == (R, 1)
    qr, sr = ref.quantize_ref(x, rnd)
    assert bool(jnp.all(q == qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    pad = ((0, (-R) % quantize.ROWS_PER_BLOCK), (0, 0))
    qp, sp = quantize.qsgd_quantize(jnp.pad(x, pad), jnp.pad(rnd, pad), interpret=True)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qp)[:R])
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sp)[:R])


def test_quantize_unbiased_with_uniform_noise():
    """E[dequant] == x under stochastic rounding (QSGD property)."""
    key = jax.random.key(3)
    x = jax.random.normal(key, (4, 256))
    outs = []
    for i in range(400):
        rnd = jax.random.uniform(jax.random.fold_in(key, i), (4, 256))
        q, s = ops.qsgd_quantize(x, rnd)
        outs.append(ops.qsgd_dequantize(q, s))
    bias = jnp.mean(jnp.stack(outs), 0) - x
    assert float(jnp.max(jnp.abs(bias))) < 0.02


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 16), st.integers(1, 8), st.integers(0, 999))
def test_policy_update_kernel_matches_alg1(K, tau, seed):
    from repro.core.pathplan import algorithm1_episode, candidate_policy_set

    key = jax.random.key(seed)
    N = 64
    pi = jax.random.dirichlet(key, jnp.ones(K), (N,)).astype(jnp.float32)
    mask = jnp.ones((N, K), bool)
    cand = candidate_policy_set(K, seed=seed)
    actions = jax.random.randint(jax.random.fold_in(key, 1), (N, tau), 0, K)
    rewards = jax.random.uniform(jax.random.fold_in(key, 2), (N, tau))
    rsums = (jax.nn.one_hot(actions, K) * rewards[..., None]).sum(1)
    out_k = ops.policy_update(pi, mask, cand, rsums, tau=tau, alpha=0.8, beta=0.4)
    out_a = algorithm1_episode(pi, mask, cand, actions, rewards, tau=tau, alpha=0.8, beta=0.4)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_a), atol=1e-5)


@pytest.mark.parametrize("shape,dtype", [((1000,), jnp.float32), ((64, 100), jnp.bfloat16), ((7, 3, 11), jnp.float32)])
def test_fused_update_sweep(shape, dtype):
    key = jax.random.key(hash(shape) % 2**31)
    w = jax.random.normal(key, shape, dtype)
    g = jax.random.normal(jax.random.fold_in(key, 1), shape, dtype)
    w0 = jax.random.normal(jax.random.fold_in(key, 2), shape, dtype)
    out = ops.fused_update(w, g, w0, lr=0.05, mu=0.1, wd=0.01)
    expect = ref.fused_update_ref(w, g, w0, 0.05, 0.1, 0.01)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), rtol=1e-2, atol=1e-2
    )
    assert out.dtype == w.dtype


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 6), st.integers(1, 9), st.integers(1, 2100), st.integers(0, 999))
def test_tree_aggregate_groups_mode_parity_property(G, C, L, seed):
    """jnp fallback == Pallas interpret == oracle on arbitrary ragged
    (G, C, L) — including C=1 (single-child groups) and tiny L."""
    prev = ops.kernel_mode()
    try:
        key = jax.random.key(seed)
        g = jax.random.normal(key, (G, C, L))
        w = jax.random.uniform(jax.random.fold_in(key, 1), (G, C))
        ops.set_kernel_mode("jnp")
        out_jnp = np.asarray(ops.tree_aggregate_groups(g, w))
        ops.set_kernel_mode("pallas")
        out_pl = np.asarray(ops.tree_aggregate_groups(g, w))
    finally:
        ops.set_kernel_mode(prev)
    expect = np.einsum("gc,gcl->gl", np.asarray(w), np.asarray(g))
    np.testing.assert_allclose(out_jnp, out_pl, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out_jnp, expect, rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 10), st.floats(0.0, 2.0), st.integers(0, 999))
def test_buffered_aggregate_mode_parity_property(K, alpha, seed):
    """Staleness-weighted apply parity across kernel modes on ragged
    pytrees down to K=1 (a single buffered commit)."""
    rng = np.random.default_rng(seed)
    ups = [
        {"a": rng.standard_normal((5, 2)).astype(np.float32),
         "b": rng.standard_normal(9).astype(np.float32)}
        for _ in range(K)
    ]
    w = list(rng.uniform(0.5, 3.0, K))
    s = list(rng.integers(0, 6, K))
    prev = ops.kernel_mode()
    try:
        ops.set_kernel_mode("jnp")
        agg_j, cw_j = ops.buffered_aggregate(ups, w, s, alpha=alpha)
        ops.set_kernel_mode("pallas")
        agg_p, cw_p = ops.buffered_aggregate(ups, w, s, alpha=alpha)
    finally:
        ops.set_kernel_mode(prev)
    for a, b in zip(jax.tree.leaves(agg_j), jax.tree.leaves(agg_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cw_j), np.asarray(cw_p), rtol=1e-6)
    disc = np.asarray(w) * (1.0 + np.asarray(s, float)) ** -alpha
    expect = (np.stack([np.concatenate([u["a"].ravel(), u["b"].ravel()]) for u in ups])
              * disc[:, None]).sum(0) / disc.sum()
    got = np.concatenate([np.asarray(l).ravel() for l in jax.tree.leaves(agg_j)])
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 5000), st.integers(0, 999))
def test_fused_update_mode_parity_property(L, seed):
    key = jax.random.key(seed)
    w = jax.random.normal(key, (L,))
    g = jax.random.normal(jax.random.fold_in(key, 1), (L,))
    w0 = jax.random.normal(jax.random.fold_in(key, 2), (L,))
    prev = ops.kernel_mode()
    try:
        ops.set_kernel_mode("jnp")
        out_j = np.asarray(ops.fused_update(w, g, w0, lr=0.05, mu=0.1, wd=0.01))
        ops.set_kernel_mode("pallas")
        out_p = np.asarray(ops.fused_update(w, g, w0, lr=0.05, mu=0.1, wd=0.01))
    finally:
        ops.set_kernel_mode(prev)
    expect = np.asarray(ref.fused_update_ref(w, g, w0, 0.05, 0.1, 0.01))
    np.testing.assert_allclose(out_j, expect, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out_p, expect, rtol=1e-5, atol=1e-5)
