"""Async buffered execution layer: staleness-aware aggregation + churn.

Covers the pluggable-scheduler refactor of ``core/sim.py``:
 - async(K=W, barrier) == synchronous engine, round for round (the
   staleness discount cancels at uniform staleness);
 - CommitDelta/ApplyBuffered verbs vs the hierarchical Aggregate;
 - staleness weighting semantics through the kernel weight vector;
 - churn injected on the event clock repairs trees (``verify_tree``);
 - pipelined dissemination never slower than synchronous level pricing;
 - empty-batch edge cases (``fused_local_training`` on no workers).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import data as data_mod
from repro.core.api import TotoroSystem
from repro.core.recovery import ReplicaStore, verify_tree
from repro.core.sim import (
    AsyncBufferScheduler,
    ChurnModel,
    SyncRoundScheduler,
    pipelined_time,
)
from repro.fl import async_engine, engine, rounds
from repro.kernels import ops as kops
from repro.kernels.tree_aggregate import staleness_weights


def build_app(seed=0, workers=8, n_nodes=150, name="async-test"):
    sys_ = TotoroSystem(zone_bits=2, suffix_bits=20, seed=seed)
    rng = np.random.default_rng(seed)
    nodes = [sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2)) for i in range(n_nodes)]
    x, y = data_mod.synthetic_classification(workers * 150, 16, 4, seed=seed)
    parts = data_mod.dirichlet_partition(y, workers, alpha=1.0, seed=seed + 1)
    parts = [p if len(p) else np.arange(3) for p in parts]
    ws = [int(w) for w in rng.choice(nodes, size=workers, replace=False)]
    app = rounds.make_app(
        sys_, name, workers=ws,
        data_by_worker={w: (x[parts[i]], y[parts[i]]) for i, w in enumerate(ws)},
        dim=16, num_classes=4, local_steps=3, lr=0.2,
    )
    return sys_, app


def test_async_k_equals_w_matches_sync_engine():
    """Equivalence property: async with K=W (barrier) reproduces the
    synchronous engine round for round — and a nonzero staleness alpha
    must not matter, because uniform staleness cancels in the mean."""
    sys_a, app_a = build_app()
    sys_s, app_s = build_app()
    W = len([w for w in sorted(app_a.handle.tree.members) if w in app_a.data])
    res = rounds.run_async(
        sys_a, [app_a], applies=3, buffer_k=W, staleness_alpha=0.7,
        model_bytes=1e5, compute_ms=25.0, barrier=True,
    )
    for _ in range(3):
        rounds.run_round(sys_s, app_s)
    assert [e.arrivals for e in res["events"]] == [W, W, W]
    assert all(e.max_staleness == 0.0 for e in res["events"])
    for la, lb in zip(jax.tree.leaves(app_a.params), jax.tree.leaves(app_s.params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-4, atol=1e-6)


def test_commit_apply_verbs_match_hierarchical_aggregate():
    """One buffer of staleness-0 commits == the hierarchical kernel
    Aggregate on the same deltas/weights."""
    sys_a, app_a = build_app(seed=2)
    sys_s, app_s = build_app(seed=2)
    ws = [w for w in sorted(app_a.handle.tree.members) if w in app_a.data]
    [(deltas, weights, _)] = engine.fused_local_training([(app_a, ws, None)])
    for w, d, wt in zip(ws, deltas, weights):
        stats = sys_a.CommitDelta(app_a.handle.app_id, w, d, weight=wt, staleness=0)
        assert stats["buffered"] >= 1 and stats["bytes"] >= 0.0
    out = sys_a.ApplyBuffered(app_a.handle.app_id, staleness_alpha=0.0)
    ref = sys_s.Aggregate(
        app_s.handle.app_id,
        {w: d for w, d in zip(ws, deltas)},
        weights={w: wt for w, wt in zip(ws, weights)},
    )
    assert out["arrivals"] == len(ws) and out["version"] == 1
    for la, lb in zip(jax.tree.leaves(out["result"]), jax.tree.leaves(ref["result"])):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-7)
    # buffer drained; below-min_k apply is a no-op
    assert sys_a.ApplyBuffered(app_a.handle.app_id)["result"] is None


def test_staleness_discount_in_kernel_weight_vector():
    """buffered_aggregate == manual 1/(1+s)^a weighted mean; alpha=0
    ignores staleness entirely."""
    rng = np.random.default_rng(0)
    ups = [rng.standard_normal(37).astype(np.float32) for _ in range(5)]
    w = [2.0, 1.0, 3.0, 1.0, 2.0]
    s = [0, 3, 1, 0, 7]
    alpha = 0.8
    agg, cw = kops.buffered_aggregate(ups, w, s, alpha=alpha)
    disc = np.asarray(w) * (1.0 + np.asarray(s, float)) ** -alpha
    ref = (np.stack(ups) * disc[:, None]).sum(0) / disc.sum()
    np.testing.assert_allclose(np.asarray(agg), ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cw), disc, rtol=1e-6)
    agg0, _ = kops.buffered_aggregate(ups, w, s, alpha=0.0)
    ref0 = (np.stack(ups) * np.asarray(w)[:, None]).sum(0) / np.sum(w)
    np.testing.assert_allclose(np.asarray(agg0), ref0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(staleness_weights(jnp.asarray(w), jnp.asarray(s, jnp.float32), alpha)),
        disc, rtol=1e-6,
    )


def test_async_no_barrier_builds_staleness_and_converges():
    """Free-running async under heterogeneous compute: fast workers lap
    slow ones (staleness > 0 appears), loss still decreases."""
    sys_, app = build_app(seed=4, workers=12)
    res = rounds.run_async(
        sys_, [app], applies=8, buffer_k=4, staleness_alpha=0.5, model_bytes=1e5,
        compute_ms=async_engine.worker_compute_fn(40.0, 6.0, seed=1),
    )
    assert len(res["events"]) == 8
    assert max(e.max_staleness for e in res["events"]) > 0
    losses = [h["loss"] for h in res["history"]]
    assert losses[-1] < losses[0]
    # event history is deterministic for a fixed build
    sys2, app2 = build_app(seed=4, workers=12)
    res2 = rounds.run_async(
        sys2, [app2], applies=8, buffer_k=4, staleness_alpha=0.5, model_bytes=1e5,
        compute_ms=async_engine.worker_compute_fn(40.0, 6.0, seed=1),
    )
    assert res["events"] == res2["events"]


def test_free_running_apply_trigger_worker_keeps_cycling():
    """Regression: the worker whose commit fills the buffer must start
    its next cycle too — with K=1 every commit applies, and the run must
    still deliver every requested apply without stalling."""
    sys_, app = build_app(seed=13, workers=4)
    res = rounds.run_async(
        sys_, [app], applies=6, buffer_k=1, staleness_alpha=0.5,
        model_bytes=1e5, compute_ms=async_engine.worker_compute_fn(40.0, 6.0, seed=2),
    )
    assert len(res["events"]) == 6
    assert all(e.arrivals == 1 for e in res["events"])


def test_barrier_with_partial_buffer_no_double_schedule():
    """Regression: barrier mode with K < W must only release workers
    idling at the barrier — mid-flight workers keep their one cycle
    (no KeyError, no leaked version refs)."""
    sys_, app = build_app(seed=14, workers=6)
    res = rounds.run_async(
        sys_, [app], applies=12, buffer_k=2, staleness_alpha=0.5, barrier=True,
        model_bytes=1e5, compute_ms=async_engine.worker_compute_fn(40.0, 6.0, seed=3),
    )
    assert len(res["events"]) == 12
    assert all(e.arrivals >= 2 for e in res["events"])
    # every snapshot version still pinned has a live in-flight reference
    trainer = res["trainer"]
    assert all(r >= 0 for r in trainer._refs[0].values())


def test_churn_in_the_loop_repairs_tree():
    """Fail/rejoin events injected mid-round via the event clock: trees
    stay verifiable, failed workers return, applies keep completing."""
    sys_, app = build_app(seed=5, workers=12, n_nodes=200)
    churn = ChurnModel(period_ms=120.0, downtime_ms=400.0, group_size=2, seed=3)
    res = rounds.run_async(
        sys_, [app], applies=6, buffer_k=4, staleness_alpha=0.5, model_bytes=1e5,
        compute_ms=async_engine.worker_compute_fn(40.0, 6.0, seed=1), churn=churn,
    )
    fails = [c for c in res["churn"] if c.kind == "fail"]
    rejoins = [c for c in res["churn"] if c.kind == "rejoin"]
    assert fails and rejoins
    assert any(c.recovery_ms > 0 for c in fails)
    assert len(res["events"]) == 6
    assert verify_tree(app.handle.tree, sys_.overlay)
    # membership accounting: exactly the not-yet-rejoined workers are out
    sched = res["scheduler"]
    all_workers = set(res["trainer"].workers(0)) | sched._failed
    live_members = {w for w in app.handle.tree.members if w in sys_.overlay.alive}
    assert live_members == all_workers - sched._failed
    assert all(w not in sys_.overlay.alive for w in sched._failed)


def test_restore_picks_ring_closest_live_holder():
    sys_, app = build_app(seed=7, n_nodes=100)
    tree = app.handle.tree
    rs = ReplicaStore(k=3)
    holders = rs.replicate(sys_.overlay, tree.app_id, tree.root, {"round": 1})
    assert len(holders) == 3
    space = sys_.overlay.space
    from repro.core.nodeid import abs_ring_distance

    def dist(h):
        return abs_ring_distance(
            space.suffix_of(h), space.suffix_of(tree.root), space.suffix_space
        )

    expect = min(holders, key=lambda h: (dist(h), h))
    got, state = rs.restore(sys_.overlay, tree.app_id, master=tree.root)
    assert got == expect and state == {"round": 1}
    # the ring-closest holder dying moves the pick to the next-closest
    sys_.overlay.fail(expect)
    rest = [h for h in holders if h != expect]
    got2, _ = rs.restore(sys_.overlay, tree.app_id, master=tree.root)
    assert got2 == min(rest, key=lambda h: (dist(h), h))


def test_pipelined_broadcast_not_slower_than_sync():
    """Store-and-forward overlap: pipelined round time <= synchronous,
    and the pipelined level cost approaches max-level as chunks grow."""
    sys_, app = build_app(seed=9, workers=24, n_nodes=300)
    handles = [app.handle]
    kw = dict(model_bytes=2e5, compute_ms=30.0)
    sync = SyncRoundScheduler(sys_, handles, **kw).run(rounds=2)
    pipe = SyncRoundScheduler(sys_, handles, pipelined=True, pipeline_chunks=8, **kw).run(rounds=2)
    for a, b in zip(sync, pipe):
        assert b.duration_ms <= a.duration_ms + 1e-9
    # formula properties: C=1 == sum; C->inf -> max; monotone in between
    ts = [7.0, 3.0, 11.0, 2.0]
    assert pipelined_time(ts, 1) == pytest.approx(sum(ts))
    assert pipelined_time(ts, 10**6) == pytest.approx(max(ts), rel=1e-4)
    assert max(ts) <= pipelined_time(ts, 64) <= pipelined_time(ts, 8) <= sum(ts)
    # tree-level pricing exposed on the forest layer too
    t_sync = app.handle.tree.broadcast_time(sys_.overlay, payload_ms=5.0)
    t_pipe = app.handle.tree.broadcast_time(sys_.overlay, payload_ms=5.0, pipelined=True)
    assert t_pipe <= t_sync


def test_sync_scheduler_trace_unchanged_by_refactor():
    """The pluggable-scheduler split must preserve the original
    MultiAppSimulator semantics: same class, same deterministic traces."""
    from repro.core.sim import MultiAppSimulator

    assert MultiAppSimulator is SyncRoundScheduler
    sys_, app = build_app(seed=11, workers=16, n_nodes=200)
    runs = [
        MultiAppSimulator(sys_, [app.handle], model_bytes=1e5, compute_ms=25.0).run(rounds=2)
        for _ in range(2)
    ]
    assert runs[0] == runs[1]
    assert [e.round for e in runs[0]] == [0, 1]


def test_pack_shards_and_local_training_empty_workers():
    """A drained commit batch must not crash the engine (max() on [])."""
    sys_, app = build_app(seed=12)
    assert engine.fused_local_training([(app, [], None)]) == [([], [], [])]
    # an empty job next to a real one keeps its slot and trains nothing
    ws = [w for w in sorted(app.handle.tree.members) if w in app.data][:2]
    out = engine.fused_local_training([(app, [], None), (app, ws, None)])
    assert out[0] == ([], [], []) and len(out[1][0]) == len(ws)
    # and the trainer's apply is a no-op on an empty pending queue
    trainer = async_engine.AsyncTrainer(sys_, [app])
    assert trainer.apply(0, 0.0) is None
