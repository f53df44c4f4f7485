"""Compressed transport: QSGD commits through the fair-share fluid model.

Locks down the compression layer end to end:

- **Quantizer properties** (hypothesis-optional, deterministic fallback
  like test_scale.py): per-element round-trip error <= scale/2 under
  deterministic rounding and < scale under stochastic rounding, on zero
  rows, ragged last chunks, and 1-element rows; |q| bounded by
  ``levels`` so the lattice always fits int8.
- **Three-way bit-exactness**: the Pallas kernel (interpret off-TPU),
  ``ref.quantize_ref``, and the pure-JAX ``fl/compression.qsgd_quantize``
  agree bit for bit under shared uniforms, in both ``kernel_mode``
  settings and for non-default ``levels``.
- **Per-commit rounding keys** (the rand=0.5 bias fix): a fixed
  (seed, app, seq) triple reproduces the wire bytes exactly; different
  sequence numbers decorrelate the rounding.
- **Fused dequant-in-aggregate**: ``buffered_aggregate_quantized``
  (per-row scales composed with staleness weights inside one
  ``tree_aggregate_groups`` call) equals the unfused
  dequantize-then-average reference.
- **Trace identity**: ``policy=None`` and ``kind="none"`` produce
  byte-identical ApplyEvent/ChurnRecord traces and fairness logs at
  M=16 — compression off must be provably free.
- **Wire conservation**: under an enabled policy every commit-direction
  flow enters ``EventCore.open_flow`` at exactly
  ``wire_bytes(model_bytes)`` (== the real ``QuantizedDelta.nbytes``),
  downloads stay full-size, nothing is left in flight, and the uplink
  byte ledger matches commits x legs x wire bytes.
- **End-to-end**: a trained qsgd-int8 run converges next to the
  uncompressed run, and mixed quantized/raw buffers are rejected.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional dev dep: the property tests widen to random draws with it
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro import tracing
from repro.core.api import TotoroSystem
from repro.core.sim import AsyncBufferScheduler, ChurnModel
from repro.fl import compression as comp
from repro.fl.compression import CompressionPolicy, QuantizedDelta
from repro.kernels import ops as kops
from repro.kernels import quantize as kq
from repro.kernels import ref


@pytest.fixture
def kernel_mode_guard():
    prev = kops.kernel_mode()
    yield
    kops.set_kernel_mode(prev)


def _rows(seed, r, c=256, kind="normal"):
    rng = np.random.default_rng(seed)
    if kind == "zeros":
        return np.zeros((r, c), np.float32)
    x = rng.normal(0, 3.0, (r, c)).astype(np.float32)
    if kind == "spiky":
        x[rng.integers(0, r, 3), rng.integers(0, c, 3)] *= 1e4
    return x


# -- round-trip error bounds ---------------------------------------------------


def _check_roundtrip(x, levels=127, key=None):
    x = jnp.asarray(x, jnp.float32)
    if key is None:
        q, s = comp.qsgd_quantize(x, levels=levels)
        bound = 0.5  # round-half-down: error <= scale/2
    else:
        q, s = comp.qsgd_quantize(x, levels=levels, key=key)
        bound = 1.0  # stochastic floor(x/s + u): error < scale
    q, s = np.asarray(q), np.asarray(s)
    assert q.dtype == np.int8
    assert np.abs(q.astype(np.int64)).max(initial=0) <= levels
    err = np.abs(np.asarray(x) - q.astype(np.float32) * s)
    # bound is per element, in units of that row's scale (+ fp slack)
    assert np.all(err <= s * bound + 1e-5 * np.maximum(s, 1.0)), (
        float((err / s).max()), bound
    )


@pytest.mark.parametrize("seed,r,kind", [
    (0, 4, "normal"), (1, 1, "normal"), (2, 8, "spiky"), (3, 4, "zeros"),
])
def test_roundtrip_deterministic_half_scale(seed, r, kind):
    _check_roundtrip(_rows(seed, r, kind=kind))


@pytest.mark.parametrize("seed,r,levels", [(0, 4, 127), (1, 2, 15), (2, 6, 1)])
def test_roundtrip_stochastic_full_scale(seed, r, levels):
    _check_roundtrip(_rows(seed, r), levels=levels, key=jax.random.PRNGKey(seed))


def test_roundtrip_one_element_rows():
    # degenerate trailing dim: scale = |x| / levels per element
    _check_roundtrip(_rows(5, 7, c=1))


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        r=st.integers(1, 12),
        levels=st.integers(1, 127),
        stochastic=st.booleans(),
    )
    def test_roundtrip_property(seed, r, levels, stochastic):
        key = jax.random.PRNGKey(seed) if stochastic else None
        _check_roundtrip(_rows(seed, r), levels=levels, key=key)


# -- Pallas == ref == pure-JAX, both kernel modes ------------------------------


@pytest.mark.parametrize("mode", ["pallas", "jnp"])
@pytest.mark.parametrize("levels", [127, 15])
def test_three_way_bit_exact_parity(kernel_mode_guard, mode, levels):
    """One set of uniforms, three implementations: lattice points bit-
    exact, scales at 1-ULP (the /levels division fuses differently per
    compile — test_kernels.py holds the same contract)."""
    x = jnp.asarray(_rows(9, 8), jnp.float32)
    rand = jax.random.uniform(jax.random.PRNGKey(3), x.shape, jnp.float32)
    kops.set_kernel_mode(mode)
    q_w, s_w = kops.qsgd_quantize(x, rand, levels=levels)
    q_r, s_r = ref.quantize_ref(x, rand, levels=levels)
    q_p, s_p = comp.qsgd_quantize(x, levels=levels, rand=rand)
    np.testing.assert_array_equal(np.asarray(q_w), np.asarray(q_r))
    np.testing.assert_array_equal(np.asarray(q_w), np.asarray(q_p))
    np.testing.assert_allclose(
        np.asarray(s_w).ravel(), np.asarray(s_r).ravel(), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(s_w).ravel(), np.asarray(s_p).ravel(), rtol=1e-6
    )


def test_pallas_kernel_direct_matches_ref():
    # the raw kernel entry point (block-aligned shapes), not the wrapper
    r = kq.ROWS_PER_BLOCK
    x = jnp.asarray(_rows(11, r), jnp.float32)
    rand = jax.random.uniform(jax.random.PRNGKey(7), x.shape, jnp.float32)
    q_k, s_k = kq.qsgd_quantize(x, rand, interpret=True, levels=31)
    q_r, s_r = ref.quantize_ref(x, rand, levels=31)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6)


# -- policy object / wire-size model -------------------------------------------


def test_policy_validation_and_as_policy():
    with pytest.raises(ValueError, match="kind"):
        CompressionPolicy(kind="gzip")
    with pytest.raises(ValueError, match="levels"):
        CompressionPolicy(kind="qsgd-int8", levels=128)
    with pytest.raises(ValueError, match="chunk"):
        CompressionPolicy(kind="qsgd-int8", chunk=0)
    with pytest.raises(TypeError):
        comp.as_policy(3.14)
    assert comp.as_policy(None) is None
    assert comp.as_policy("qsgd-int8") == CompressionPolicy(kind="qsgd-int8")
    p = CompressionPolicy(kind="qsgd-int8")
    assert comp.as_policy(p) is p
    assert not CompressionPolicy().enabled and p.enabled


@pytest.mark.parametrize("n,chunk", [(1, 256), (256, 256), (257, 256), (5000, 256),
                                     (7, 64), (64, 64), (100, 3)])
def test_wire_bytes_matches_real_quantized_delta(n, chunk):
    """The scheduler's pricing model == the actual serialized size."""
    policy = CompressionPolicy(kind="qsgd-int8", chunk=chunk)
    delta = {"w": np.random.default_rng(n).normal(size=n).astype(np.float32)}
    qd = comp.quantize_delta(delta, policy, key=jax.random.PRNGKey(0))
    assert qd.nbytes == policy.wire_bytes(4.0 * n)
    rows = math.ceil(n / chunk)
    assert qd.nbytes == rows * chunk + rows * 4
    # compression actually compresses once a full f32 row is in play
    if n >= chunk:
        assert qd.nbytes < 4.0 * n


def test_wire_bytes_none_is_float_identity():
    p = CompressionPolicy()
    assert p.wire_bytes(1.5e6) == float(1.5e6)


def test_quantize_delta_roundtrip_pytree_and_padding():
    rng = np.random.default_rng(0)
    delta = {
        "a": rng.normal(size=(13, 7)).astype(np.float32),
        "b": rng.normal(size=(5,)).astype(np.float32),
    }
    policy = CompressionPolicy(kind="qsgd-int8")
    qd = comp.quantize_delta(delta, policy, key=jax.random.PRNGKey(1))
    assert qd.length == 13 * 7 + 5
    back = comp.dequantize_delta(qd)
    assert set(back) == {"a", "b"}
    assert back["a"].shape == (13, 7) and back["b"].shape == (5,)
    # rows chunk the FLATTENED pytree, so the error bound is the global
    # max-abs (one 96-element delta -> one row, one shared scale)
    s_max = max(np.abs(v).max() for v in delta.values()) / policy.levels
    for k in delta:
        assert np.abs(back[k] - delta[k]).max() < s_max + 1e-6
    # padding elements (zeros) quantize to exactly 0: floor(0 + u) = 0
    pad = np.asarray(qd.q).ravel()[qd.length:]
    assert np.all(pad == 0)


def test_commit_key_reproduces_and_decorrelates():
    policy = CompressionPolicy(kind="qsgd-int8", seed=5)
    delta = {"w": np.random.default_rng(2).normal(size=700).astype(np.float32)}
    k0 = comp.commit_key(policy, 0, 0)
    qa = comp.quantize_delta(delta, policy, k0)
    qb = comp.quantize_delta(delta, policy, comp.commit_key(policy, 0, 0))
    np.testing.assert_array_equal(qa.q, qb.q)  # fixed triple: exact bytes
    np.testing.assert_array_equal(qa.scale, qb.scale)
    # consecutive commits (and sibling apps) draw different rounding bits
    qc = comp.quantize_delta(delta, policy, comp.commit_key(policy, 0, 1))
    qd = comp.quantize_delta(delta, policy, comp.commit_key(policy, 1, 0))
    assert not np.array_equal(qa.q, qc.q)
    assert not np.array_equal(qa.q, qd.q)
    np.testing.assert_array_equal(qa.scale, qc.scale)  # scales are rand-free


# -- one grid program per quantize == the eager sequence it replaced ----------


def _eager_quantize(delta, chunk, key, levels):
    """The oracle: the eager flatten, pad, draw and padded kernel call
    that ``quantize_delta`` ran as separate dispatches before the grid
    program folded them together."""
    leaves, _ = jax.tree.flatten(delta)
    flat = jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])
    n = int(flat.size)
    rows = max(1, math.ceil(n / chunk))
    x2d = jnp.zeros((rows * chunk,), jnp.float32).at[:n].set(flat).reshape(rows, chunk)
    if key is None:
        rand = jnp.full((rows, chunk), 0.5, jnp.float32)
    else:
        rand = jax.random.uniform(key, (rows, chunk), jnp.float32)
    if kops.kernel_mode() == "jnp":
        q, s = kops._qsgd_quantize_jnp(x2d, rand, levels=levels)
    else:
        pad = ((0, (-rows) % kq.ROWS_PER_BLOCK), (0, 0))
        q, s = kq.qsgd_quantize(jnp.pad(x2d, pad), jnp.pad(rand, pad), interpret=True,
                                levels=levels)
    return np.asarray(q)[:rows], np.asarray(s)[:rows], n


_GRID_CASES = {
    # FedAvg's MNIST 2NN, 784-200-200-10: 199,210 values on 779 rows
    "mnist-2nn": {"w1": (784, 200), "b1": (200,), "w2": (200, 200), "b2": (200,),
                  "w3": (200, 10), "b3": (10,)},
    "ragged": {"a": (13, 7), "b": (5,)},
    "exact-rows": {"w": (4, 128)},
    "one-element": {"s": ()},
    "bf16": {"w": (300,), "b": (7,)},
}


def _grid_delta(case, seed=0):
    rng = np.random.default_rng(seed)
    delta = {k: rng.normal(0, 0.1, s).astype(np.float32) for k, s in _GRID_CASES[case].items()}
    if case == "bf16":
        delta["w"] = delta["w"].astype(jnp.bfloat16)
    return delta


def _same(qd, oracle, delta):
    q, s, n = oracle
    np.testing.assert_array_equal(qd.q, q)
    np.testing.assert_array_equal(qd.scale, s)
    assert qd.length == n
    assert qd.shapes == tuple(np.shape(l) for l in jax.tree.leaves(delta))


@pytest.mark.parametrize("mode", ["jnp", "pallas"])
@pytest.mark.parametrize("rounding", ["commit", "broadcast", "key", "half"])
@pytest.mark.parametrize("case", list(_GRID_CASES))
def test_grid_program_bit_identical_to_eager_sequence(kernel_mode_guard, mode, rounding, case):
    kops.set_kernel_mode(mode)
    delta = _grid_delta(case)
    for levels in (127, 7):
        if rounding == "broadcast":
            policy = CompressionPolicy(kind="qsgd-int8", seed=11, downlink="delta-qsgd",
                                       downlink_levels=levels)
            qd = comp.quantize_broadcast_delta(delta, policy, app=2, version=9)
            oracle = _eager_quantize(delta, 256, comp.broadcast_key(policy, 2, 9), levels)
        else:
            policy = CompressionPolicy(kind="qsgd-int8", seed=11, levels=levels)
            if rounding == "commit":
                qd = comp.quantize_delta(delta, policy, app=3, seq=40)
                key = comp.commit_key(policy, 3, 40)
            else:
                key = jax.random.PRNGKey(5) if rounding == "key" else None
                qd = comp.quantize_delta(delta, policy, key)
            oracle = _eager_quantize(delta, 256, key, levels)
        assert qd.levels == levels
        _same(qd, oracle, delta)


def test_grid_program_compiles_once_per_shape_set():
    """Counters are traced: ten commits across three apps and five
    broadcast versions run one compiled grid program per model shape."""
    policy = CompressionPolicy(kind="qsgd-int8", seed=4, downlink="delta-qsgd")
    before = comp._grid._cache_size()
    for shapes in ({"w": (37, 11), "b": (11,)}, {"w": (41, 13), "b": (13,)}):
        rng = np.random.default_rng(0)
        delta = {k: rng.normal(size=s).astype(np.float32) for k, s in shapes.items()}
        qs = [comp.quantize_delta(delta, policy, app=i % 3, seq=i) for i in range(10)]
        qs += [comp.quantize_broadcast_delta(delta, policy, app=1, version=v) for v in range(5)]
        assert len({q.q.tobytes() for q in qs}) == len(qs)  # every key differs
        before += 1
        assert comp._grid._cache_size() == before


def test_rounding_key_or_its_counters():
    policy = CompressionPolicy(kind="qsgd-int8", downlink="qsgd-int8")
    delta = {"w": np.ones(5, np.float32)}
    with pytest.raises(ValueError, match="not both"):
        comp.quantize_delta(delta, policy, jax.random.PRNGKey(0), app=0, seq=0)
    with pytest.raises(ValueError, match="both counters"):
        comp.quantize_delta(delta, policy, app=0)
    with pytest.raises(ValueError, match="both counters"):
        comp.quantize_broadcast_delta(delta, policy, version=3)


# -- fused dequantize-in-aggregate ---------------------------------------------


@pytest.mark.parametrize("mode", ["jnp", "pallas"])
def test_fused_aggregate_matches_unfused_reference(kernel_mode_guard, mode):
    """agg = sum_k w_k * (q_k * s_k) / sum_k w_k with the staleness
    discount folded into the kernel's weight vector — compare against the
    plain dequantize-then-average done in float64 on the host."""
    kops.set_kernel_mode(mode)
    rng = np.random.default_rng(4)
    policy = CompressionPolicy(kind="qsgd-int8")
    K, n = 5, 600
    qds, weights, staleness = [], [], []
    for k in range(K):
        delta = {"w": rng.normal(0, 2.0, n).astype(np.float32)}
        qds.append(comp.quantize_delta(delta, policy, jax.random.PRNGKey(k)))
        weights.append(float(rng.uniform(0.5, 2.0)))
        staleness.append(float(k % 3))
    alpha = 0.5
    flat, combined = kops.buffered_aggregate_quantized(
        [q.q for q in qds], [q.scale for q in qds], weights, staleness,
        alpha=alpha,
    )
    w = np.asarray([wt / (1.0 + s) ** alpha for wt, s in zip(weights, staleness)])
    deq = np.stack([
        (np.asarray(q.q, np.float64) * np.asarray(q.scale, np.float64)).ravel() for q in qds
    ])
    expect = (w[:, None] * deq).sum(0) / w.sum()
    np.testing.assert_allclose(np.asarray(flat), expect, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(combined), w, rtol=1e-6)


def _eager_apply(qds, weights, staleness, alpha):
    """The quantized apply one eager operation at a time, as the verb ran
    it before its glue was compiled: the oracle of the compiled path.
    Returns (result pytree, combined weights)."""
    from repro.kernels.tree_aggregate import staleness_weights

    w = staleness_weights(weights, staleness, alpha)
    q = jnp.stack([jnp.asarray(d.q) for d in qds]).astype(jnp.float32)
    s = jnp.stack([jnp.asarray(d.scale).reshape(-1) for d in qds])
    g = jnp.transpose(q, (1, 0, 2))
    gw = jnp.transpose(w[:, None] * s)
    agg = kops.tree_aggregate_groups(g, gw) / jnp.maximum(w.sum(), 1e-12)
    return qds[0].unflatten(np.asarray(jnp.ravel(agg))), [float(x) for x in w]


@pytest.mark.parametrize("alpha", [0.0, 0.5])
@pytest.mark.parametrize("K", [1, 3, 4, 5])
def test_compiled_apply_verb_bit_identical_to_eager(kernel_mode_guard, K, alpha):
    """``ApplyBuffered``'s compiled quantized path (operands in one
    program, the kernel, normalize-and-unflatten in one program) gives
    the eager composition's result and weights bit for bit, with the
    result left on the device."""
    kops.set_kernel_mode("jnp")
    rng = np.random.default_rng(K)
    policy = CompressionPolicy(kind="qsgd-int8")
    shapes = {"w": (37, 11), "b": (11,), "v": (300,)}
    sys_, handles = _build_handles(1, workers=K, n_nodes=20, seed=K)
    h = handles[0]
    qds, weights, staleness = [], [], []
    for k, w in enumerate(sorted(h.tree.members)[:K]):
        delta = {n: rng.normal(0, 1.0, s).astype(np.float32) for n, s in shapes.items()}
        qds.append(comp.quantize_delta(delta, policy, app=0, seq=k))
        weights.append(float(rng.uniform(0.5, 3.0)))
        staleness.append(k % 3)
        sys_.CommitDelta(h.app_id, w, qds[-1], weight=weights[-1], staleness=staleness[-1])
    out = sys_.ApplyBuffered(h.app_id, staleness_alpha=alpha)
    want, want_w = _eager_apply(qds, weights, staleness, alpha)
    assert out["weights"] == want_w
    for name, shape in shapes.items():
        got = out["result"][name]
        assert isinstance(got, jax.Array) and got.shape == shape
        np.testing.assert_array_equal(np.asarray(got), want[name])


# -- commit payloads stay on the device ----------------------------------------


@pytest.fixture
def tracer():
    tracing.disable()
    tracing.clear()
    yield tracing
    tracing.disable()
    tracing.clear()


def _host(qd):
    """``qd`` with q and scale pulled to the host: the commit payload as
    ``quantize_delta`` returned it when it pulled both."""
    return dataclasses.replace(qd, q=np.asarray(qd.q), scale=np.asarray(qd.scale))


def _pulled_stack_apply(qds, weights, staleness, alpha):
    """The oracle: the verb's composition when commits held host grids,
    the K grids and scales stacked with numpy, uploaded as one stack each
    and handed to ``buffered_aggregate_quantized``.  Returns (result
    pytree, combined weights)."""
    leaves, w = kops.buffered_aggregate_quantized(
        jnp.asarray(np.stack([np.asarray(d.q) for d in qds])),
        jnp.asarray(np.stack([np.asarray(d.scale) for d in qds])),
        weights, staleness, alpha=alpha, shapes=qds[0].shapes,
    )
    return jax.tree.unflatten(qds[0].treedef, leaves), np.asarray(w).tolist()


@pytest.mark.parametrize("K,alpha,grids", [
    *[(K, alpha, "device") for K in (1, 3, 4, 5) for alpha in (0.0, 0.5)],
    (5, 0.5, "mixed"),
])
def test_apply_verb_on_device_grids_bit_identical_to_pulled_stack(
    kernel_mode_guard, tracer, K, alpha, grids
):
    """``ApplyBuffered`` aggregates the commits' device grids where they
    are and gives the pulled-and-stacked composition's result and weights
    bit for bit; a host grid in the buffer is uploaded and counted."""
    kops.set_kernel_mode("jnp")
    rng = np.random.default_rng(10 + K)
    policy = CompressionPolicy(kind="qsgd-int8")
    shapes = {"w": (37, 11), "b": (11,), "v": (300,)}
    sys_, handles = _build_handles(1, workers=K, n_nodes=20, seed=K)
    h = handles[0]
    qds, weights, staleness, on_host = [], [], [], 0
    for k, w in enumerate(sorted(h.tree.members)[:K]):
        delta = {n: rng.normal(0, 1.0, s).astype(np.float32) for n, s in shapes.items()}
        qds.append(comp.quantize_delta(delta, policy, app=0, seq=k))
        assert isinstance(qds[-1].q, jax.Array) and isinstance(qds[-1].scale, jax.Array)
        weights.append(float(rng.uniform(0.5, 3.0)))
        staleness.append(k % 3)
        payload = qds[-1]
        if grids == "mixed" and k % 2:
            payload, on_host = _host(payload), on_host + 1
        sys_.CommitDelta(h.app_id, w, payload, weight=weights[-1], staleness=staleness[-1])
    tracer.enable()
    before = tracer.snapshot()
    out = sys_.ApplyBuffered(h.app_id, staleness_alpha=alpha)
    tracer.disable()
    grew = {k: v - before[k] for k, v in tracer.snapshot().items()}
    assert grew["grid_resident"] == K - on_host
    assert grew["grid_uploaded"] == on_host
    assert grew["apply_fused"] == 1 and grew["apply_eager"] == 0
    assert grew["h2d_pushes"] == 2 * on_host
    assert grew["d2h_pulls"] == 1  # the combined weights
    want, want_w = _pulled_stack_apply(qds, weights, staleness, alpha)
    assert out["weights"] == want_w
    for name, shape in shapes.items():
        got = out["result"][name]
        assert isinstance(got, jax.Array) and got.shape == shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want[name]))


@pytest.mark.parametrize("kind", ["qsgd-int8", "signsgd", "topk"])
def test_quantize_delta_keeps_q_and_scale_on_the_device(kernel_mode_guard, tracer, kind):
    """Every commit kind returns the quantize's device arrays, pulls
    nothing, and carries the bits and the sizes the pulled arrays had."""
    kops.set_kernel_mode("jnp")
    policy = CompressionPolicy(kind=kind, seed=3, topk_frac=0.05)
    delta = _grid_delta("mnist-2nn", seed=4)
    n = sum(np.size(l) for l in jax.tree.leaves(delta))
    rows = math.ceil(n / 256)
    tracer.enable()
    before = tracer.snapshot()
    qd = comp.quantize_delta(delta, policy, app=1, seq=7)
    tracer.disable()
    assert tracer.snapshot()["d2h_pulls"] == before["d2h_pulls"]
    assert not [r for r in tracer.records if r[0] == "xfer.d2h"]
    assert isinstance(qd.q, jax.Array) and isinstance(qd.scale, jax.Array)
    assert qd.q.dtype == jnp.int8 and qd.q.shape == (rows, 256)
    assert qd.scale.dtype == jnp.float32 and qd.scale.shape == (rows, 1)
    # the same call, pulled as the commit path used to pull it
    pulled = _host(comp.quantize_delta(delta, policy, app=1, seq=7))
    np.testing.assert_array_equal(np.asarray(qd.q), pulled.q)
    np.testing.assert_array_equal(np.asarray(qd.scale), pulled.scale)
    assert qd.nbytes == pulled.nbytes
    assert qd.wire_nbytes == pulled.wire_nbytes
    assert qd.nbytes == policy.wire_bytes(4.0 * n)
    assert (qd.length, qd.shapes, qd.levels, qd.chunk) == (
        pulled.length, pulled.shapes, pulled.levels, pulled.chunk)
    if kind == "qsgd-int8":
        assert qd.wire_nbytes is None
        _same(qd, _eager_quantize(delta, 256, comp.commit_key(policy, 1, 7), policy.levels),
              delta)
    else:
        assert qd.wire_nbytes == policy.wire_bytes(4.0 * n)
    if kind == "signsgd":
        flat = np.concatenate([np.ravel(l) for l in jax.tree.leaves(delta)])
        grid = np.zeros(rows * 256, np.float32)
        grid[:n] = flat
        np.testing.assert_array_equal(np.asarray(qd.q).ravel(), np.sign(grid).astype(np.int8))


def _ef_trainer():
    """One app of 4 workers under qsgd-int8 with EF-SGD, after two
    applies: every worker commits twice, so the second commit folds the
    first one's residual."""
    from repro import data as data_mod
    from repro.fl import async_engine, rounds

    sys_ = TotoroSystem(zone_bits=2, suffix_bits=20, seed=5)
    rng = np.random.default_rng(5)
    nodes = [sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2)) for i in range(40)]
    x, y = data_mod.synthetic_classification(4 * 30, 16, 4, seed=5)
    parts = np.array_split(np.arange(len(y)), 4)
    ws = [int(w) for w in rng.choice(nodes, size=4, replace=False)]
    app = rounds.make_app(
        sys_, "ef", workers=ws,
        data_by_worker={w: (x[parts[i]], y[parts[i]]) for i, w in enumerate(ws)},
        dim=16, num_classes=4, local_steps=2, lr=0.2, seed=5,
    )
    policy = CompressionPolicy(kind="qsgd-int8", levels=7, error_feedback=True)
    tr = async_engine.AsyncTrainer(sys_, [app], compression=policy)
    for _ in range(2):
        for w in tr.workers(0):
            tr.begin_download(0, w)
        for w in tr.workers(0):
            tr.commit(0, w, 0.0)
        tr.apply(0, 0.0)
    return tr


def test_error_feedback_residual_bit_identical_with_device_payloads(monkeypatch):
    """EF-SGD dequantizes each commit to carry its residual: with the
    payload on the device the residuals after two commits per worker,
    and the params, equal those of host payloads bit for bit."""
    from repro.fl import async_engine

    dev = _ef_trainer()
    with monkeypatch.context() as m:
        m.setattr(async_engine, "quantize_delta",
                  lambda *a, **kw: _host(comp.quantize_delta(*a, **kw)))
        host = _ef_trainer()
    assert dev._ef[0] and sorted(dev._ef[0]) == sorted(host._ef[0])
    for w in dev._ef[0]:
        for a, b in zip(jax.tree.leaves(dev._ef[0][w]), jax.tree.leaves(host._ef[0][w])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(dev.apps[0].params), jax.tree.leaves(host.apps[0].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- scheduler fixtures --------------------------------------------------------


def _build_handles(m, workers=4, n_nodes=160, seed=0, compression=None):
    """Timing-only fixture: M dataflow trees over one shared overlay."""
    sys_ = TotoroSystem(zone_bits=2, suffix_bits=22, seed=seed)
    rng = np.random.default_rng(seed)
    nodes = [
        sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2),
                  bandwidth=float(rng.uniform(20, 100)))
        for i in range(n_nodes)
    ]
    handles = []
    for a in range(m):
        h = sys_.CreateTree(f"comp-{m}-{a}", compression=compression)
        for w in rng.choice(nodes, size=workers, replace=False):
            sys_.Subscribe(h.app_id, int(w))
        handles.append(h)
    return sys_, handles


def _trace(m, *, compression, seed=0, applies=2, churn=True,
           model_bytes=2e5, **sched_kw):
    sys_, handles = _build_handles(m, seed=seed)
    sched = AsyncBufferScheduler(
        sys_, handles, model_bytes=model_bytes, compute_ms=25.0, buffer_k=3,
        churn=ChurnModel(period_ms=400.0, downtime_ms=600.0, group_size=2, seed=9)
        if churn else None,
        app_compression=compression, **sched_kw,
    )
    events = sched.run(applies, max_events=500_000)
    return events, list(sched.churn_log), list(sched.fairness_log), sched


# -- policy=none trace identity ------------------------------------------------


def test_m16_policy_none_trace_byte_identical():
    """Compression off must be free: the default (no policy) and an
    explicit kind="none" policy produce the same ApplyEvents,
    ChurnRecords and fairness log, byte for byte."""
    base = _trace(16, compression=None)
    off = _trace(16, compression=CompressionPolicy(kind="none"))
    assert base[0] == off[0]  # exact ApplyEvent equality
    assert base[1] == off[1]  # exact ChurnRecord equality
    assert base[2] == off[2]  # fairness log: uplink bytes, jain, rates


def test_policy_none_identity_under_legacy_and_sampled_pricing():
    kw = dict(congestion_mode="sampled", churn=False)
    base = _trace(4, compression=None, **kw)
    off = _trace(4, compression="none", **kw)
    assert base[:3] == off[:3]


def test_handle_compression_feeds_scheduler_and_arg_overrides():
    sys_, handles = _build_handles(
        2, compression=CompressionPolicy(kind="qsgd-int8")
    )
    sched = AsyncBufferScheduler(sys_, handles, model_bytes=1e6)
    assert all(p is not None and p.enabled for p in sched._compression)
    assert sched._commit_bytes[0] == handles[0].compression.wire_bytes(1e6)
    # explicit arg beats the handle attribute
    sched2 = AsyncBufferScheduler(
        sys_, handles, model_bytes=1e6, app_compression="none"
    )
    assert sched2._commit_bytes == [1e6, 1e6]


# -- compressed-path wire conservation -----------------------------------------


def test_compressed_flows_priced_at_exact_wire_bytes():
    """Every commit-direction flow opens at wire_bytes(model_bytes)
    (== the serialized QuantizedDelta size), downloads stay full-size,
    and the ledger closes: no in-flight flows, uplink bytes == commit
    legs x wire bytes — exact conservation across join/complete
    repricing."""
    model_bytes = 1.5e6
    policy = CompressionPolicy(kind="qsgd-int8")
    wire_mbit = policy.wire_bytes(model_bytes) * 8e-6
    full_mbit = model_bytes * 8e-6
    assert wire_mbit < 0.3 * full_mbit

    sys_, handles = _build_handles(3, seed=1)
    sched = AsyncBufferScheduler(
        sys_, handles, model_bytes=model_bytes, compute_ms=25.0, buffer_k=3,
        app_compression=policy,
    )
    opened = []
    orig = sched.open_flow
    sched.open_flow = lambda sender, mbit, **kw: (
        opened.append(float(mbit)), orig(sender, mbit, **kw)
    )[1]
    sched.run(2, max_events=4_000_000)
    assert opened, "fair mode must route transfers through open_flow"
    # exactly two flow sizes exist: full-model downloads, compressed commits
    assert set(opened) == {full_mbit, wire_mbit}
    commits = sum(1 for m in opened if m == wire_mbit)
    assert commits > 0
    # conservation: anything still in flight at shutdown is partially
    # delivered against exactly one of the two flow sizes; completed
    # flows were drained in full by _finish_flow (delivered == total)
    for f in sched._flows.values():
        assert f.total_mbit in (full_mbit, wire_mbit)
        assert f.delivered_mbit <= f.total_mbit + 1e-12
    # the uplink ledger is commit-leg granular at the compressed size:
    # every credited commit leg contributed exactly wire_bytes
    stats = sched.transport_stats()
    credited = sum(stats["uplink_bytes"])
    assert credited > 0
    assert credited / policy.wire_bytes(model_bytes) == pytest.approx(
        round(credited / policy.wire_bytes(model_bytes))
    )
    assert credited <= commits * policy.wire_bytes(model_bytes)


def test_compressed_run_moves_fewer_bytes_and_finishes_sooner():
    base = _trace(4, compression=None, churn=False)
    qsgd = _trace(4, compression="qsgd-int8", churn=False)
    b_stats, q_stats = base[3].transport_stats(), qsgd[3].transport_stats()
    assert sum(q_stats["uplink_bytes"]) < 0.3 * sum(b_stats["uplink_bytes"])
    # commits travel ~4x faster, so every app's applies complete earlier
    assert all(
        q <= b for q, b in zip(q_stats["done_ms"], b_stats["done_ms"])
    )


# -- data-plane integration ----------------------------------------------------


def test_mixed_quantized_raw_buffer_rejected():
    sys_, handles = _build_handles(1, workers=2, n_nodes=20, seed=3)
    h = handles[0]
    raw = {"w": np.ones(4, np.float32)}
    qd = comp.quantize_delta(
        raw, CompressionPolicy(kind="qsgd-int8"), jax.random.PRNGKey(0)
    )
    ws = sorted(h.tree.members)[:2]
    sys_.CommitDelta(h.app_id, ws[0], raw, weight=1.0, staleness=0)
    sys_.CommitDelta(h.app_id, ws[1], qd, weight=1.0, staleness=0)
    with pytest.raises(ValueError, match="mixed quantized and raw"):
        sys_.ApplyBuffered(h.app_id)


def test_apply_buffered_all_quantized_matches_raw_aggregate():
    """Same deltas through the quantized and raw ApplyBuffered paths:
    results agree to quantization error (scale/levels per element)."""
    rng = np.random.default_rng(6)
    policy = CompressionPolicy(kind="qsgd-int8")
    deltas = [{"w": rng.normal(0, 1.0, 300).astype(np.float32)} for _ in range(3)]
    out = []
    for quantize in (False, True):
        sys_, handles = _build_handles(1, workers=3, n_nodes=20, seed=4)
        h = handles[0]
        for i, (w, d) in enumerate(zip(sorted(h.tree.members)[:3], deltas)):
            payload = (
                comp.quantize_delta(d, policy, jax.random.PRNGKey(i))
                if quantize else d
            )
            sys_.CommitDelta(h.app_id, w, payload, weight=1.0, staleness=i % 2)
        out.append(sys_.ApplyBuffered(h.app_id, staleness_alpha=0.5))
    raw, quant = out
    assert raw["weights"] == pytest.approx(quant["weights"])
    scale_bound = max(np.abs(d["w"]).max() for d in deltas) / policy.levels
    np.testing.assert_allclose(
        quant["result"]["w"], raw["result"]["w"], atol=scale_bound + 1e-6
    )


def _train_async(compression, seed=0):
    from benchmarks.common import build_system
    from repro import data as data_mod
    from repro.fl import async_engine, rounds

    sys_, nodes, rng = build_system(n_nodes=80, zones=3, seed=seed)
    apps = []
    for a in range(2):
        x, y = data_mod.synthetic_classification(6 * 24, 16, 4, seed=100 + a)
        parts = data_mod.dirichlet_partition(y, 6, alpha=1.0, seed=200 + a)
        ws = [int(n) for n in rng.choice(nodes, size=6, replace=False)]
        apps.append(rounds.make_app(
            sys_, f"tc-{a}", workers=ws,
            data_by_worker={n: (x[parts[i]], y[parts[i]]) for i, n in enumerate(ws)},
            dim=16, num_classes=4, local_steps=2, lr=0.2, seed=a,
        ))
    return async_engine.run_async(
        sys_, apps, applies=5, buffer_k=4, model_bytes=4e5,
        compute_ms=20.0, compression=compression,
    )


def test_trained_qsgd_converges_close_to_uncompressed():
    base = _train_async(None)
    qsgd = _train_async("qsgd-int8")
    f_base = np.mean([r["loss"] for r in base["history"][-2:]])
    f_qsgd = np.mean([r["loss"] for r in qsgd["history"][-2:]])
    assert np.isfinite(f_qsgd)
    assert abs(f_qsgd - f_base) <= 1e-1  # tiny fixture; bench gates 1e-2
    # the data plane really shipped QuantizedDeltas: commit seqs advanced
    tr = qsgd["trainer"]
    assert all(s > 0 for s in tr._commit_seq)
    # and the compressed run's commits were priced smaller
    q_up = sum(qsgd["scheduler"].transport_stats()["uplink_bytes"])
    b_up = sum(base["scheduler"].transport_stats()["uplink_bytes"])
    assert q_up < 0.3 * b_up


def test_trained_policy_none_trace_identical_to_default():
    base = _train_async(None)
    off = _train_async(CompressionPolicy(kind="none"))
    assert base["events"] == off["events"]
    assert base["churn"] == off["churn"]
    assert [r["loss"] for r in base["history"]] == [
        r["loss"] for r in off["history"]
    ]


# -- bench registration --------------------------------------------------------


def test_bench_compression_registered():
    from benchmarks.run import REGISTRY

    names = [n for n, _, _ in REGISTRY]
    mods = [m for _, m, _ in REGISTRY]
    assert "compression" in names
    assert "benchmarks.bench_compression" in mods
