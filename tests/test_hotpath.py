"""Hot-path overhaul: exactness + boundedness of the optimized engines.

Covers:
 - incremental repricing (UplinkState) is exact: byte-identical event
   traces vs the full-water-filling oracle (tests/oracles.py), bit-
   identical rates on the uncapped fast path, allclose on caps;
 - EventCore.cancel no longer leaks dead heap entries for the run:
   lazy-deletion compaction keeps the heap bounded under churn-heavy
   cancellation (the satellite regression);
 - numpy-resident transfer pricing == the jitted CongestionEnv lookup;
 - compiled-vs-interpret kernel parity (tree_aggregate_groups,
   buffered_aggregate, fused_update) on ragged / 1-sample shapes;
 - megabatched + bucketed training matches the per-worker reference on
   ragged/1-sample shards and per-app training; recompile count per run
   is O(#buckets), asserted via the jit cache-miss counter and
   cross-checked against jax's own jit cache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from oracles import (
    WaterFillingScheduler,
    reference_fused_local_training,
    reference_local_training,
)

from repro import data as data_mod
from repro.core.api import TotoroSystem
from repro.core.congestion import CongestionEnv, UplinkState, fair_share_rates
from repro.core.sim import AsyncBufferScheduler, ChurnModel
from repro.fl import async_engine, engine, rounds
from repro.kernels import ops as kops
from repro.kernels import ref


# ---------------------------------------------------------------------------
# helpers


def build_multi_app(m=3, workers=6, n_nodes=120, seed=0, shard=20):
    sys_ = TotoroSystem(zone_bits=2, suffix_bits=20, seed=seed)
    rng = np.random.default_rng(seed)
    nodes = [
        sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2))
        for i in range(n_nodes)
    ]
    apps = []
    for a in range(m):
        x, y = data_mod.synthetic_classification(workers * shard, 16, 4, seed=100 + a)
        parts = data_mod.dirichlet_partition(y, workers, alpha=0.5, seed=200 + a)
        ws = [int(n) for n in rng.choice(nodes, size=workers, replace=False)]
        apps.append(
            rounds.make_app(
                sys_, f"hot-{a}", workers=ws,
                data_by_worker={n: (x[parts[i]], y[parts[i]]) for i, n in enumerate(ws)},
                dim=16, num_classes=4, local_steps=2, lr=0.2, seed=a,
            )
        )
    return sys_, apps


@pytest.fixture
def kernel_mode_guard():
    prev = kops.kernel_mode()
    yield
    kops.set_kernel_mode(prev)


# ---------------------------------------------------------------------------
# incremental repricing: exactness


def test_uplink_state_uncapped_bit_identical_to_water_filling():
    rng = np.random.default_rng(0)
    for trial in range(25):
        n = int(rng.integers(1, 12))
        weights = rng.uniform(0.1, 4.0, n)
        groups = rng.integers(0, 3, n)
        st = UplinkState(73.5)
        for fid in range(n):
            st.add(fid, float(weights[fid]), None, ("grp", int(groups[fid])))
        gn = {g: int((groups == g).sum()) for g in set(groups.tolist())}
        expect = fair_share_rates(
            73.5, [float(weights[i]) / gn[int(groups[i])] for i in range(n)]
        )
        assert st.rates() == expect  # bit-for-bit, not just close


def test_uplink_state_capped_matches_progressive_water_filling():
    rng = np.random.default_rng(1)
    for trial in range(40):
        n = int(rng.integers(1, 10))
        weights = rng.uniform(0.1, 4.0, n)
        caps = [
            None if rng.random() < 0.4 else float(rng.uniform(0.5, 30.0))
            for _ in range(n)
        ]
        groups = rng.integers(0, 3, n)
        st = UplinkState(50.0)
        for fid in range(n):
            st.add(fid, float(weights[fid]), caps[fid], ("grp", int(groups[fid])))
        gn = {g: int((groups == g).sum()) for g in set(groups.tolist())}
        expect = fair_share_rates(
            50.0,
            [float(weights[i]) / gn[int(groups[i])] for i in range(n)],
            [None if caps[i] is None else caps[i] / gn[int(groups[i])] for i in range(n)],
        )
        got = st.rates()
        np.testing.assert_allclose(got, expect, rtol=1e-9, atol=1e-9)
        # conservation: never allocate above capacity
        assert sum(got) <= 50.0 * (1 + 1e-9)


def test_uplink_state_add_remove_keeps_order_and_counts():
    st = UplinkState(100.0)
    for fid in range(6):
        st.add(fid, 1.0 + fid, 10.0 * (fid + 1) if fid % 2 else None, ("grp", fid % 2))
    st.remove(3)
    st.remove(0)
    assert len(st) == 4
    # remaining flows keep insertion order (1, 2, 4, 5)
    assert list(st._flows) == [1, 2, 4, 5]
    st2 = UplinkState(100.0)
    for fid in (1, 2, 4, 5):
        st2.add(fid, 1.0 + fid, 10.0 * (fid + 1) if fid % 2 else None, ("grp", fid % 2))
    assert st.rates() == st2.rates()


def test_incremental_trace_byte_identical_with_churn():
    """The repricing exactness gate, in miniature: same apply events, same
    churn log, same defer/fairness telemetry as the water-filling oracle."""
    results = []
    for cls in (WaterFillingScheduler, AsyncBufferScheduler):
        sys_, apps = build_multi_app(seed=3)
        churn = ChurnModel(period_ms=90.0, downtime_ms=300.0, group_size=2, seed=5)
        sched = cls(
            sys_, [a.handle for a in apps], model_bytes=1.5e5,
            compute_ms=async_engine.worker_compute_fn(40.0, 6.0, seed=1),
            buffer_k=3, churn=churn,
        )
        events = sched.run(6)
        results.append((events, list(sched.churn_log), sched.transport_stats()))
    (ev_a, churn_a, tp_a), (ev_b, churn_b, tp_b) = results
    assert ev_a == ev_b  # exact dataclass equality incl. float timestamps
    assert churn_a == churn_b
    assert tp_a == tp_b


def test_incremental_trace_identical_with_caps_weights_admission():
    from repro.core.sim import RelayAdmission

    results = []
    for cls in (WaterFillingScheduler, AsyncBufferScheduler):
        sys_, apps = build_multi_app(seed=7, m=3)
        sched = cls(
            sys_, [a.handle for a in apps], model_bytes=2e5,
            compute_ms=async_engine.worker_compute_fn(40.0, 6.0, seed=2),
            buffer_k=3,
            app_weights=[2.0, 1.0, 1.0],
            app_rate_caps=[None, 40.0, 25.0],
            relay_admission=RelayAdmission(threshold=0.6, alpha=0.8),
        )
        events = sched.run(5)
        results.append((events, list(sched.defer_log)))
    assert results[0] == results[1]


# ---------------------------------------------------------------------------
# heap compaction (satellite regression)


def test_cancel_compacts_dead_heap_entries():
    sys_, apps = build_multi_app(m=1, workers=2, n_nodes=40)
    core = AsyncBufferScheduler(
        sys_, [a.handle for a in apps], model_bytes=1e5, buffer_k=1
    )
    core._reset_clock()
    seqs = [core.schedule(1000.0 + i, lambda t: None) for i in range(500)]
    for s in seqs[:-1]:
        core.cancel(s)
    # lazy deletion is bounded: dead entries can never exceed the live
    # ones by more than the compaction threshold
    assert len(core._heap) < 200
    assert core._dead * 2 <= len(core._heap) or core._dead <= 64
    # double-cancel must not double-count
    before = core._dead
    core.cancel(seqs[0])
    assert core._dead == before


def test_churn_heavy_run_keeps_heap_bounded():
    """Churn cancels in-flight cycles every period; with per-flow events
    and no compaction the heap grew monotonically with every reprice.
    Bound: peak heap stays within a small multiple of live entities."""
    sys_, apps = build_multi_app(m=4, workers=6, seed=11)
    churn = ChurnModel(
        period_ms=60.0, downtime_ms=200.0, group_size=3, seed=2,
        max_fail_events=60,
    )
    sched = AsyncBufferScheduler(
        sys_, [a.handle for a in apps], model_bytes=2e5,
        compute_ms=async_engine.worker_compute_fn(40.0, 6.0, seed=3),
        buffer_k=3, churn=churn,
    )
    sched.run(12)
    live_entities = 4 * 6 + 4  # worker cycles + per-app bookkeeping
    assert sched.heap_max <= 8 * live_entities
    assert sched.events_dispatched > 0


def test_compaction_preserves_event_order():
    sys_, apps = build_multi_app(m=1, workers=2, n_nodes=40)
    core = AsyncBufferScheduler(
        sys_, [a.handle for a in apps], model_bytes=1e5, buffer_k=1
    )
    core._reset_clock()
    fired = []
    keep = []
    for i in range(300):
        seq = core.schedule(float(300 - i), lambda t, i=i: fired.append((t, i)))
        if i % 7:
            core.cancel(seq)
        else:
            keep.append((float(300 - i), i))
    core.run_events()
    assert fired == sorted(keep)


# ---------------------------------------------------------------------------
# numpy transfer pricing == jitted congestion lookup


def test_transfer_ms_matches_jitted_latency():
    sys_, apps = build_multi_app(m=2, workers=5, seed=13)
    core = AsyncBufferScheduler(
        sys_, [a.handle for a in apps], model_bytes=3e5, buffer_k=2
    )
    rng = np.random.default_rng(0)
    n = len(core._cap_f32)
    for trial in range(10):
        own = rng.integers(0, n, size=rng.integers(1, 9)).astype(np.int32)
        extra = rng.integers(0, n, size=rng.integers(0, 9)).astype(np.int32)
        core._active = {0: extra} if len(extra) else {}
        actions = np.concatenate([own, extra]) if len(extra) else own
        lat = np.asarray(core.env.latency_ms(jnp.asarray(actions)))[: len(own)]
        assert core.transfer_ms(own, reduce="max") == float(lat.max())
        assert core.transfer_ms(own, reduce="sum") == float(lat.sum())
    core._active = {}


# ---------------------------------------------------------------------------
# compiled-vs-interpret kernel parity (ragged / 1-sample shapes)


@pytest.mark.parametrize("G,C,L", [(1, 1, 17), (3, 1, 1024), (5, 7, 333), (2, 9, 2048)])
def test_tree_aggregate_groups_parity_modes(kernel_mode_guard, G, C, L):
    key = jax.random.key(G * 1000 + C * 100 + L)
    g = jax.random.normal(key, (G, C, L))
    w = jax.random.uniform(jax.random.fold_in(key, 1), (G, C))
    outs = {}
    for mode in ("jnp", "pallas"):
        kops.set_kernel_mode(mode)
        outs[mode] = np.asarray(kops.tree_aggregate_groups(g, w))
    expect = np.einsum("gc,gcl->gl", np.asarray(w), np.asarray(g))
    np.testing.assert_allclose(outs["jnp"], outs["pallas"], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(outs["jnp"], expect, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", [1, 2, 5, 9])
def test_buffered_aggregate_parity_modes(kernel_mode_guard, k):
    rng = np.random.default_rng(k)
    ups = [
        {"a": rng.standard_normal((7, 3)).astype(np.float32),
         "b": rng.standard_normal(11).astype(np.float32)}
        for _ in range(k)
    ]
    w = list(rng.uniform(0.5, 3.0, k))
    s = list(rng.integers(0, 5, k))
    outs = {}
    for mode in ("jnp", "pallas"):
        kops.set_kernel_mode(mode)
        agg, cw = kops.buffered_aggregate(ups, w, s, alpha=0.7)
        outs[mode] = (np.asarray(jnp.concatenate([jnp.ravel(l) for l in jax.tree.leaves(agg)])), np.asarray(cw))
    np.testing.assert_allclose(outs["jnp"][0], outs["pallas"][0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs["jnp"][1], outs["pallas"][1], rtol=1e-6)
    disc = np.asarray(w) * (1.0 + np.asarray(s, float)) ** -0.7
    ref_agg = (np.stack([np.concatenate([u["a"].ravel(), u["b"].ravel()]) for u in ups])
               * disc[:, None]).sum(0) / disc.sum()
    np.testing.assert_allclose(outs["jnp"][0], ref_agg, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("L,dtype", [(1, jnp.float32), (1000, jnp.float32), (2048, jnp.bfloat16)])
def test_fused_update_parity_modes_and_donation(kernel_mode_guard, L, dtype):
    key = jax.random.key(L)
    w = jax.random.normal(key, (L,), dtype)
    g = jax.random.normal(jax.random.fold_in(key, 1), (L,), dtype)
    w0 = jax.random.normal(jax.random.fold_in(key, 2), (L,), dtype)
    expect = np.asarray(ref.fused_update_ref(w, g, w0, 0.05, 0.1, 0.01), np.float32)
    outs = {}
    for mode in ("jnp", "pallas"):
        kops.set_kernel_mode(mode)
        outs[mode] = np.asarray(
            kops.fused_update(w, g, w0, lr=0.05, mu=0.1, wd=0.01), np.float32
        )
    np.testing.assert_allclose(outs["jnp"], expect, rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(outs["pallas"], expect, rtol=1e-2, atol=1e-2)
    # donation: same result, donated buffer consumed (fallback path)
    kops.set_kernel_mode("jnp")
    w_d = jnp.array(w)  # fresh buffer we are allowed to give up
    out_d = kops.fused_update(w_d, g, w0, lr=0.05, mu=0.1, wd=0.01, donate=True)
    np.testing.assert_allclose(np.asarray(out_d, np.float32), outs["jnp"], rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# megabatched / bucketed training: equivalence + recompile bound


def test_bucketed_training_matches_exact_and_reference_ragged():
    """Ragged shards incl. a 1-sample worker: the bucketed (W, B)
    padding of the per-worker-params megabatch reproduces the per-worker
    reference loop."""
    sys_, apps = build_multi_app(m=1, workers=5, seed=17)
    app = apps[0]
    ws = sorted(app.data)
    # force heavy raggedness: shrink shards to 1..n samples
    for i, w in enumerate(ws):
        x, y = app.data[w]
        n = max(1, min(len(y), 1 + 3 * i))
        app.data[w] = (x[:n], y[:n])
    d_ref, wt_ref, l_ref = reference_local_training(app, ws)
    [(d_mega, wt_mega, l_mega)] = engine.fused_local_training(
        [(app, ws, app.params)]
    )
    assert wt_ref == wt_mega
    np.testing.assert_allclose(l_mega, l_ref, rtol=1e-4, atol=1e-6)
    for dr, dv in zip(d_ref, d_mega):
        for a, b in zip(jax.tree.leaves(dr), jax.tree.leaves(dv)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )


def test_fused_cross_app_training_matches_per_app():
    sys_, apps = build_multi_app(m=3, workers=4, seed=19)
    jobs = [(a, sorted(a.data), a.params) for a in apps]
    fused = engine.fused_local_training(jobs)
    for job, (d_f, wt_f, l_f) in zip(jobs, fused):
        [(d_e, wt_e, l_e)] = engine.fused_local_training([job])
        assert wt_f == wt_e
        np.testing.assert_allclose(l_f, l_e, rtol=1e-4, atol=1e-6)
        for df, de in zip(d_f, d_e):
            for a, b in zip(jax.tree.leaves(df), jax.tree.leaves(de)):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
                )


def test_fused_training_splits_same_name_different_shape_models():
    """Regression: two apps sharing a model NAME (and steps/lr/mu/feat)
    but differing in num_classes must land in different fusion groups —
    the params signature is part of the key, not just the name."""
    sys_ = TotoroSystem(zone_bits=2, suffix_bits=20, seed=0)
    rng = np.random.default_rng(0)
    nodes = [sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2)) for i in range(60)]
    apps = []
    for a, classes in enumerate((4, 10)):
        x, y = data_mod.synthetic_classification(3 * 12, 16, classes, seed=50 + a)
        parts = data_mod.dirichlet_partition(y, 3, alpha=1.0, seed=60 + a)
        ws = [int(n) for n in rng.choice(nodes, size=3, replace=False)]
        apps.append(
            rounds.make_app(
                sys_, f"shapes-{a}", workers=ws,
                data_by_worker={n: (x[parts[i]], y[parts[i]]) for i, n in enumerate(ws)},
                dim=16, num_classes=classes, local_steps=2, lr=0.2, seed=a,
            )
        )
    jobs = [(a, sorted(a.data), a.params) for a in apps]
    fused = engine.fused_local_training(jobs)  # crashed before the fix
    for job, (d_f, wt_f, l_f) in zip(jobs, fused):
        [(d_e, wt_e, l_e)] = engine.fused_local_training([job])
        assert wt_f == wt_e
        np.testing.assert_allclose(l_f, l_e, rtol=1e-4, atol=1e-6)
        for df, de in zip(d_f, d_e):
            for a, b in zip(jax.tree.leaves(df), jax.tree.leaves(de)):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
                )


def test_run_round_fused_matches_run_round():
    sys_a, apps_a = build_multi_app(m=2, workers=4, seed=23)
    sys_b, apps_b = build_multi_app(m=2, workers=4, seed=23)
    fused = rounds.run_round_fused(sys_a, apps_a)
    plain = [rounds.run_round(sys_b, app) for app in apps_b]
    assert len(fused) == len(plain)
    for mf, mp, aa, ab in zip(fused, plain, apps_a, apps_b):
        assert mf["round"] == mp["round"]
        assert mf["loss"] == pytest.approx(mp["loss"], rel=1e-5, abs=1e-7)
        assert mf["time_ms"] == pytest.approx(mp["time_ms"])
        for la, lb in zip(jax.tree.leaves(aa.params), jax.tree.leaves(ab.params)):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-7
            )


def test_async_recompiles_bounded_by_buckets():
    """The jit cache-miss gate: a churny multi-app async run with ragged
    version groups must stay at one fused dispatch per apply and
    O(#buckets) compiles, cross-checked against jax's own jit cache."""
    sys_, apps = build_multi_app(m=3, workers=6, seed=29)
    churn = ChurnModel(period_ms=120.0, downtime_ms=360.0, group_size=2, seed=1)
    engine.DISPATCH.reset()
    cache_before = engine.megabatched_local_train._cache_size()
    res = async_engine.run_async(
        sys_, apps, applies=4, buffer_k=3, staleness_alpha=0.5,
        model_bytes=1.5e5, compute_ms=async_engine.worker_compute_fn(40.0, 6.0, seed=4),
        churn=churn,
    )
    applies = len(res["history"])
    assert applies >= 3 * 4  # every app completed its applies
    assert engine.DISPATCH.dispatches == applies  # ONE fused dispatch per apply
    # bucket bound: one static config, W in {1..bucket(6)}, B bucketed
    bound = (int(np.log2(8)) + 1) * 4
    assert engine.DISPATCH.compiles <= bound
    cache_delta = engine.megabatched_local_train._cache_size() - cache_before
    assert cache_delta <= engine.DISPATCH.compiles


def test_async_megabatch_matches_legacy_dispatch_loop(monkeypatch):
    """Trace + loss equivalence of the fused apply vs the per-worker
    reference loop (tests/oracles.py) in its place."""
    outs = []
    for oracle in (False, True):
        if oracle:
            monkeypatch.setattr(engine, "fused_local_training", reference_fused_local_training)
        sys_, apps = build_multi_app(m=2, workers=5, seed=31)
        res = async_engine.run_async(
            sys_, apps, applies=3, buffer_k=3, staleness_alpha=0.5,
            model_bytes=1.5e5,
            compute_ms=async_engine.worker_compute_fn(40.0, 6.0, seed=5),
        )
        outs.append((res, apps))
    (res_a, apps_a), (res_b, apps_b) = outs
    assert res_a["events"] == res_b["events"]
    la = [r["loss"] for r in res_a["history"]]
    lb = [r["loss"] for r in res_b["history"]]
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-7)
    for aa, ab in zip(apps_a, apps_b):
        for pa, pb in zip(jax.tree.leaves(aa.params), jax.tree.leaves(ab.params)):
            np.testing.assert_allclose(
                np.asarray(pa), np.asarray(pb), rtol=1e-4, atol=1e-6
            )
