"""FL substrate: fedavg/fedprox math, compression, DP, rounds, steps."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import data as data_mod
from repro.fl import aggregation, compression, dp


def test_fedavg_weighted_mean():
    deltas = [{"w": jnp.ones((3,)) * i} for i in range(1, 4)]
    out = aggregation.fedavg(deltas, [1.0, 1.0, 2.0])
    np.testing.assert_allclose(np.asarray(out["w"]), (1 + 2 + 3 * 2) / 4 * np.ones(3))


def test_pairwise_accumulate_matches_fedavg():
    key = jax.random.key(0)
    deltas = [{"w": jax.random.normal(jax.random.fold_in(key, i), (5,))} for i in range(4)]
    w = np.array([0.1, 0.2, 0.3, 0.4])
    acc = None
    for d, wi in zip(deltas, w):
        acc = aggregation.pairwise_accumulate(acc, d, float(wi))
    expect = aggregation.fedavg(deltas, list(w))
    np.testing.assert_allclose(np.asarray(acc["w"]), np.asarray(expect["w"]), rtol=1e-6)


def test_fedprox_gradient_term():
    g = {"w": jnp.zeros(3)}
    p = {"w": jnp.ones(3) * 2.0}
    w0 = {"w": jnp.ones(3)}
    out = aggregation.fedprox_grad(g, p, w0, mu=0.5)
    np.testing.assert_allclose(np.asarray(out["w"]), 0.5 * np.ones(3))
    out0 = aggregation.fedprox_grad(g, p, w0, mu=0.0)
    np.testing.assert_allclose(np.asarray(out0["w"]), np.zeros(3))


def test_straggler_mask_renormalizes():
    w = aggregation.straggler_mask([1.0, 1.0, 2.0], [True, False, True])
    np.testing.assert_allclose(np.asarray(w), [1 / 3, 0.0, 2 / 3])


def test_signsgd_and_error_feedback():
    key = jax.random.key(1)
    x = jax.random.normal(key, (8, 256))
    s, scale = compression.signsgd_compress(x)
    assert s.dtype == jnp.int8 and bool(jnp.all(jnp.abs(s) <= 1))
    # error feedback: accumulated residual shrinks the long-run bias
    err = jnp.zeros_like(x)
    recon_sum = jnp.zeros_like(x)
    for i in range(50):
        (c, sc), err = compression.error_feedback_update(x, err, compression.signsgd_compress)
        recon_sum = recon_sum + c.astype(jnp.float32) * sc
    bias = recon_sum / 50 - x
    assert float(jnp.mean(jnp.abs(bias))) < float(jnp.mean(jnp.abs(x))) * 0.3


def test_dp_clip_and_noise():
    g = {"w": jnp.ones((100,)) * 10}
    clipped, n = dp.clip_by_global_norm(g, 1.0)
    assert float(dp.global_norm(clipped)) <= 1.0 + 1e-5
    noised = dp.dp_sanitize(g, jax.random.key(0), clip=1.0, sigma=0.1)
    assert float(dp.global_norm(noised)) > 0


def test_dirichlet_partition_covers_all_and_skews():
    _, y = data_mod.synthetic_classification(3000, 16, 10, seed=0)
    parts = data_mod.dirichlet_partition(y, 10, alpha=0.1, seed=0)
    all_idx = np.concatenate(parts)
    assert len(all_idx) == 3000 and len(np.unique(all_idx)) == 3000
    # low alpha -> skewed: some client has a dominant class
    fracs = []
    for p in parts:
        if len(p) < 20:
            continue
        counts = np.bincount(y[p], minlength=10)
        fracs.append(counts.max() / counts.sum())
    assert max(fracs) > 0.5


def test_data_streams_deterministic_and_shard_disjoint():
    sc = data_mod.StreamConfig(vocab_size=100, seq_len=8, batch_per_shard=4, seed=1)
    a = data_mod.lm_batch(sc, shard=0, step=5)
    b = data_mod.lm_batch(sc, shard=0, step=5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = data_mod.lm_batch(sc, shard=1, step=5)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_full_fl_round_over_overlay_converges():
    from repro.core.api import TotoroSystem
    from repro.fl import rounds

    sys_ = TotoroSystem(zone_bits=2, suffix_bits=20, seed=0)
    rng = np.random.default_rng(0)
    nodes = [sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2)) for i in range(150)]
    x, y = data_mod.synthetic_classification(1200, 16, 4, seed=0)
    parts = data_mod.dirichlet_partition(y, 8, alpha=1.0, seed=1)
    workers = [int(w) for w in rng.choice(nodes, size=8, replace=False)]
    app = rounds.make_app(
        sys_, "test", workers=workers,
        data_by_worker={w: (x[parts[i]], y[parts[i]]) for i, w in enumerate(workers)},
        dim=16, num_classes=4, local_steps=4, lr=0.3,
    )
    accs = []
    for _ in range(5):
        rounds.run_round(sys_, app)
        accs.append(rounds.evaluate(app, x[:300], y[:300]))
    assert accs[-1] > 0.8, accs
    assert accs[-1] > accs[0] - 0.05


def test_q8_cross_pod_math_single_device():
    """q8_mean_over_pods == plain mean up to one quantization step."""
    from repro.fl.steps import q8_mean_over_pods

    key = jax.random.key(0)
    g = {"w": jax.random.normal(key, (2, 64, 32))}  # (pods, ...)
    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    with jax.set_mesh(mesh):
        agg = jax.jit(q8_mean_over_pods)(g)
    expect = jnp.mean(g["w"], axis=0)
    step = jnp.max(jnp.abs(g["w"])) / 127
    assert float(jnp.max(jnp.abs(agg["w"] - expect))) <= float(step) + 1e-5
