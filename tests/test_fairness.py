"""Weighted-fair transfer pricing + staleness-aware relay admission.

Covers the PR-4 starvation fix end-to-end:
 - fluid re-pricing is progress-preserving and matches the closed-form
   processor-sharing schedule (join and complete both re-price);
 - re-pricing conserves delivered bytes exactly (no work lost or
   duplicated), and per-app uplink accounting equals commits x hops;
 - a single-flow (never contended) async trace matches the closed
   form: each leg takes its bits over each hop's capacity, plus the
   per-hop base latency, summed over the hops;
 - per-app weight and rate-cap knobs shape contended throughput;
 - relay admission defers stale commits when contended, never drops
   them, and feeds the selector's deadline signal;
 - fairness telemetry lands in ``AppHandle.round_records`` (transport:
   per-app uplink bytes/throughput + Jain's index);
 - liveness regressions: a churn fail that shrinks effective K below
   the already-buffered commits applies immediately instead of
   stalling; the force-admit guard drains the selector blocklist;
 - ``AdaptiveKController`` rate EMA survives a full-outage commit gap
   (K recovers after rejoin);
 - ``dirichlet_partition(min_samples=...)`` never emits empty clients,
   and the engine's masked-padding path matches the per-worker
   reference on heavily ragged shards.
"""
import numpy as np
import pytest
from oracles import reference_local_training

from repro import data as data_mod
from repro.core.api import TotoroSystem
from repro.core.congestion import fair_share_rates
from repro.core.sim import (
    AdaptiveKController,
    AsyncBufferScheduler,
    ChurnModel,
    EventCore,
    RelayAdmission,
)
from repro.fl import async_engine, engine, rounds
from repro.fl.selection import UtilitySelector
from repro.kernels.ops import jain_fairness


def build_app(seed=0, workers=8, n_nodes=150, name="fair-test"):
    sys_ = TotoroSystem(zone_bits=2, suffix_bits=20, seed=seed)
    rng = np.random.default_rng(seed)
    nodes = [sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2)) for i in range(n_nodes)]
    x, y = data_mod.synthetic_classification(workers * 150, 16, 4, seed=seed)
    parts = data_mod.dirichlet_partition(y, workers, alpha=1.0, seed=seed + 1)
    ws = [int(w) for w in rng.choice(nodes, size=workers, replace=False)]
    app = rounds.make_app(
        sys_, name, workers=ws,
        data_by_worker={w: (x[parts[i]], y[parts[i]]) for i, w in enumerate(ws)},
        dim=16, num_classes=4, local_steps=3, lr=0.2,
    )
    return sys_, app


def build_handles(m, workers=6, n_nodes=120, seed=0, bw=None):
    """Timing-only multi-app fixture: trees + subscriptions, no trainer."""
    sys_ = TotoroSystem(zone_bits=2, suffix_bits=20, seed=seed)
    rng = np.random.default_rng(seed)
    nodes = [
        sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2),
                  bandwidth=bw if bw is not None else float(rng.uniform(20, 100)))
        for i in range(n_nodes)
    ]
    handles = []
    for a in range(m):
        h = sys_.CreateTree(f"h{a}")
        for w in rng.choice(nodes, size=workers, replace=False):
            sys_.Subscribe(h.app_id, int(w))
        handles.append(h)
    return sys_, handles


class _BareOverlay:
    def __init__(self, bandwidth):
        self.bandwidth = dict(enumerate(bandwidth))

    def nodes(self):
        return sorted(self.bandwidth)


class _BareSystem:
    def __init__(self, bandwidth):
        self.overlay = _BareOverlay(bandwidth)


# -- the fluid engine ---------------------------------------------------------


def test_fair_share_rates_weighted_caps_waterfill():
    assert fair_share_rates(100.0, [1, 1]) == [50.0, 50.0]
    assert fair_share_rates(100.0, [3, 1]) == [75.0, 25.0]
    # a bound cap frees capacity for the uncapped flow
    assert fair_share_rates(100.0, [1, 1], [10.0, None]) == [10.0, 90.0]
    r = fair_share_rates(100.0, [1, 1, 2], [5.0, None, None])
    assert r[0] == 5.0 and r[1] == pytest.approx(95.0 / 3) and r[2] == pytest.approx(190.0 / 3)
    # degenerate inputs
    assert fair_share_rates(100.0, []) == []
    assert fair_share_rates(100.0, [1.0]) == [100.0]


def test_repricing_matches_processor_sharing_closed_form():
    """The tentpole bug, both directions: flow A starts alone (must NOT
    keep its solo rate after B joins), flow B starts contended (must NOT
    keep the half rate after A completes)."""
    core = EventCore(_BareSystem([80.0]), [], model_bytes=1e6)  # 8 mbit payload
    done = {}
    core.schedule(0.0, lambda t: core.open_flow(0, 8.0, on_done=lambda t: done.setdefault("A", t)))
    core.schedule(40.0, lambda t: core.open_flow(0, 8.0, on_done=lambda t: done.setdefault("B", t)))
    core.run_events()
    # A: 40ms solo at 80 Mbps -> 3.2 mbit, then 4.8 mbit at 40 Mbps -> t=160.
    # B: by t=160 has 4.8 mbit, remaining 3.2 at the full 80 -> t=200.
    assert done["A"] == pytest.approx(160.0)
    assert done["B"] == pytest.approx(200.0)
    # conservation across both re-prices: nothing left in flight
    assert core._flows == {} and core._flows_by_sender == {}


def test_flow_groups_split_one_share():
    """Two flows of one app against one flow of another: the app's
    aggregate share is its weight, not its flow count."""
    core = EventCore(_BareSystem([90.0]), [], model_bytes=1e6)
    done = {}
    for name, group in (("a1", "A"), ("a2", "A"), ("b", "B")):
        core.open_flow(0, 9.0, on_done=lambda t, n=name: done.setdefault(n, t), group=group)
    core.run_events()
    # app A: 45 Mbps split over two 9-mbit flows (22.5 each); app B: 45 alone.
    # B finishes at 200ms; A's flows tie, then... both still need 4.5 mbit at
    # t=200, now splitting the full 90 -> 45 each -> +100ms.
    assert done["b"] == pytest.approx(200.0)
    assert done["a1"] == pytest.approx(300.0) and done["a2"] == pytest.approx(300.0)


def test_single_flow_async_trace_identical_fair_vs_legacy():
    """Acceptance: uncontended (single-flow) pricing — one worker of one
    app never overlaps two transfers, so each leg takes, per hop, the
    base latency plus its bits over the hop's capacity, summed over the
    hops, and each apply lands one download + compute + upload cycle
    after the last."""
    model_bytes, compute_ms = 1e5, 25.0
    sys_, app = build_app(seed=3, workers=1)
    res = rounds.run_async(
        sys_, [app], applies=4, buffer_k=1, staleness_alpha=0.5,
        model_bytes=model_bytes, compute_ms=compute_ms,
    )
    sched = res["scheduler"]
    (w,) = app.data
    down = sched._path_senders(0, w, up=False)
    up = sched._path_senders(0, w, up=True)
    assert len(down) and len(up)  # a real multi-hop path, not the root

    def leg_ms(hops):
        return sum(
            sched.base_ms + 1e3 * model_bytes * 8e-6 / sched._cap_mbps[h] for h in hops
        )

    cycle = leg_ms(down) + compute_ms + leg_ms(up)
    times = [e.time_ms for e in res["events"]]
    assert times == pytest.approx([cycle * (i + 1) for i in range(4)], rel=1e-12)
    assert [h["t_ms"] for h in res["history"]] == times


def test_fair_mode_deterministic_and_conserves_uplink_bytes():
    """Contended fair runs are deterministic, and per-app uplink bytes
    equal exactly commits x path-hops x model_bytes — re-pricing moved
    completion times around but neither lost nor duplicated work."""
    model_bytes = 2e5

    def once():
        sys_, handles = build_handles(4, workers=6, seed=5)
        sched = AsyncBufferScheduler(
            sys_, handles, model_bytes=model_bytes, compute_ms=10.0, buffer_k=3,
        )
        sched.run(4)
        return sched

    a, b = once(), once()
    assert a.history == b.history and a.history
    assert any(e.max_staleness >= 0 for e in a.history)
    for ai in range(4):
        expect = sum(
            cyc * len(a._path_senders(ai, w, up=True))
            for (i, w), cyc in a._cycle.items()
            if i == ai
        ) * model_bytes
        # commit-granular accounting: exactly one leg's bytes per
        # completed cycle, every re-price included, nothing duplicated
        assert a._uplink_bytes[ai] == pytest.approx(expect)
    # horizon_ms stops the clock mid-run (fixed-window measurements)
    sys_, handles = build_handles(4, workers=6, seed=5)
    cut = AsyncBufferScheduler(
        sys_, handles, model_bytes=model_bytes, compute_ms=10.0, buffer_k=3,
    )
    cut.run(10**6, horizon_ms=200.0)
    assert cut.now >= 200.0 and not all(cut._done)
    assert cut.now <= max(e.time_ms for e in a.history)


def test_app_weights_and_rate_caps_shape_throughput():
    """Same workload, one shared bottleneck: the heavier app finishes
    first; a rate cap slows the capped app down."""
    def run(**kw):
        sys_, handles = build_handles(2, workers=5, n_nodes=40, seed=9, bw=50.0)
        sched = AsyncBufferScheduler(
            sys_, handles, model_bytes=8e5, compute_ms=5.0, buffer_k=3, **kw
        )
        sched.run(5)
        return sched.transport_stats()

    even = run()
    heavy0 = run(app_weights=[4.0, 1.0])
    # weighting app 0 up must speed it up relative to the even split
    assert heavy0["done_ms"][0] < even["done_ms"][0]
    capped0 = run(app_rate_caps=[5.0, None])
    assert capped0["done_ms"][0] > even["done_ms"][0]
    # and the handle attribute is an equivalent spelling of the knob
    sys_, handles = build_handles(2, workers=5, n_nodes=40, seed=9, bw=50.0)
    handles[0].transfer_weight = 4.0
    sched = AsyncBufferScheduler(sys_, handles, model_bytes=8e5, compute_ms=5.0, buffer_k=3)
    sched.run(5)
    assert sched.transport_stats()["done_ms"][0] == pytest.approx(heavy0["done_ms"][0])
    # a zero share would price transfers at rate 0 forever: rejected
    with pytest.raises(ValueError):
        AsyncBufferScheduler(
            sys_, handles, model_bytes=8e5, compute_ms=5.0, buffer_k=3,
            app_weights=[0.0, 1.0],
        )
    with pytest.raises(ValueError):
        AsyncBufferScheduler(
            sys_, handles, model_bytes=8e5, compute_ms=5.0, buffer_k=3,
            app_rate_caps=[-1.0, None],
        )


# -- relay admission ----------------------------------------------------------


def test_relay_admission_defers_stale_commits_but_never_drops():
    sys_, handles = build_handles(6, workers=6, n_nodes=60, seed=11, bw=40.0)
    adm = RelayAdmission(threshold=0.9, alpha=1.0, max_defer_ms=120.0)
    sched = AsyncBufferScheduler(
        sys_, handles, model_bytes=6e5, compute_ms=5.0, buffer_k=2, relay_admission=adm,
    )
    events = sched.run(6, max_events=3_000_000)
    # every app still completes every apply (deferral delays, never drops)
    per_app = {}
    for e in events:
        per_app[e.app_id] = per_app.get(e.app_id, 0) + 1
    assert all(v == 6 for v in per_app.values())
    assert sched.defer_log, "contended stale commits should have been deferred"
    for d in sched.defer_log:
        assert 0.0 <= d.waited_ms <= adm.max_defer_ms + 1e-6
    # an uncontended (single-app, single-worker) run never defers
    sys2, h2 = build_handles(1, workers=1, n_nodes=40, seed=11)
    s2 = AsyncBufferScheduler(
        sys2, h2, model_bytes=6e5, compute_ms=5.0, buffer_k=1, relay_admission=adm,
    )
    s2.run(4)
    assert s2.defer_log == []


def test_relay_admission_feeds_selector_deadline_signal():
    sel = UtilitySelector(deadline_ms=1e9, seed=0)  # never parks on its own
    sys_, handles = build_handles(6, workers=6, n_nodes=60, seed=11, bw=40.0)
    adm = RelayAdmission(threshold=0.9, alpha=1.0, max_defer_ms=120.0)
    sched = AsyncBufferScheduler(
        sys_, handles, model_bytes=6e5, compute_ms=5.0, buffer_k=2,
        relay_admission=adm, selector=sel,
    )
    sched.run(6, max_events=3_000_000)
    assert sched.defer_log
    deferred = {(d.app_idx, d.worker) for d in sched.defer_log}
    stats = [sel._s(ai, w) for ai, w in deferred]
    assert all(st.defers >= 1 for st in stats)
    # the hold time reaches the deadline term through the cycle
    # wall-clock (on_commit spans the deferral); on_defer records the
    # attribution EMA, which decays again as undeferred commits land
    worst = max(d.waited_ms for d in sched.defer_log)
    assert any(st.defer_ms > 0 for st in stats) and worst > 0
    st = sel._s(0, 10**9)
    sel.on_defer(0, 10**9, 0.0, 80.0)
    before = st.defer_ms
    sel.on_commit(0, 10**9, 1.0, 10.0)
    assert 0.0 < st.defer_ms < before


# -- fairness telemetry -------------------------------------------------------


def test_jain_fairness_formula():
    assert jain_fairness([1.0, 1.0, 1.0]) == pytest.approx(1.0)
    assert jain_fairness([1.0, 0.0, 0.0]) == pytest.approx(1.0 / 3.0)
    x = np.asarray([3.0, 1.0, 2.0, 0.5])
    assert jain_fairness(x) == pytest.approx(float(x.sum() ** 2 / (len(x) * (x**2).sum())))
    assert jain_fairness([]) == 1.0 and jain_fairness([0.0, 0.0]) == 1.0


def test_transport_records_land_in_round_records():
    sys_, app = build_app(seed=6, workers=8)
    res = rounds.run_async(
        sys_, [app], applies=4, buffer_k=3, staleness_alpha=0.5, model_bytes=1e5,
        compute_ms=async_engine.worker_compute_fn(40.0, 6.0, seed=2),
    )
    recs = app.handle.round_records
    assert len(recs) == 4
    for rec in recs:
        tp = rec["transport"]
        assert tp["app_id"] == app.handle.app_id
        assert tp["uplink_bytes"] > 0 and tp["uplink_mbps"] > 0
        assert 0.0 < tp["jain_uplink"] <= 1.0
    # bytes are monotone across applies, and the scheduler-side log agrees
    bs = [r["transport"]["uplink_bytes"] for r in recs]
    assert bs == sorted(bs)
    sched = res["scheduler"]
    assert [f["uplink_bytes"] for f in sched.fairness_log] == bs
    stats = sched.transport_stats()
    assert set(stats) == {
        "uplink_bytes", "downlink_bytes", "uplink_mbps", "done_ms",
        "jain_uplink", "deferred_commits",
    }
    assert all(b > 0 for b in stats["downlink_bytes"])


# -- liveness under churn (satellite regressions) -----------------------------


def test_churn_fail_applies_buffer_that_already_meets_shrunk_k():
    """Regression: K=W barrier round, one slow worker; churn kills a
    worker after the other three committed.  Effective K clamps to 3 ==
    buffered commits, but no further commit event will ever fire — the
    old scheduler stalled until the failed worker rejoined (downtime is
    set absurdly high to expose it); the fixed one applies at fail time."""
    sys_, handles = build_handles(1, workers=4, n_nodes=60, seed=21, bw=60.0)

    def compute(handle, worker, cycle):
        return 8000.0 if worker == min(sorted(handle.tree.members)) else 10.0

    churn = ChurnModel(
        period_ms=2000.0, downtime_ms=1e9, group_size=1, seed=0, max_fail_events=1,
    )
    sched = AsyncBufferScheduler(
        sys_, handles, model_bytes=1e5, compute_ms=compute, buffer_k=4,
        barrier=True, churn=churn,
    )
    events = sched.run(1, max_events=200_000)
    assert len(events) == 1
    assert events[0].time_ms < 1e6, "apply must not wait for the rejoin"
    assert events[0].arrivals == 3


def test_unrelated_fail_does_not_restart_barrier_idlers():
    """Regression (review find): a churn fail in app B must not hand
    app A's committed barrier idlers a second cycle inside the same
    round — _kick only restarts idlers when it fired the apply itself.
    With the bug, a fast worker commits twice and the K=W round applies
    without the straggler."""
    sys_, handles = build_handles(2, workers=4, n_nodes=60, seed=23, bw=60.0)

    class FixedVictim(ChurnModel):
        def __init__(self, victim, **kw):
            super().__init__(**kw)
            self._victim = victim

        def pick_victims(self, pool):
            return [self._victim] if self._victim in pool else []

    members0, members1 = set(handles[0].tree.members), set(handles[1].tree.members)
    only1 = sorted(members1 - members0 - {handles[1].tree.root})
    assert len(only1) >= 2, "fixture needs two app-1-only non-root workers"
    slow0 = min(sorted(members0 - members1))
    slow1, victim = only1[0], only1[1]  # app 1 stays alive past the fail

    def compute(handle, worker, cycle):
        if handle.app_id == handles[0].app_id:
            return 8000.0 if worker == slow0 else 10.0
        return 8000.0 if worker == slow1 else 10.0

    churn = FixedVictim(victim, period_ms=2000.0, downtime_ms=1e9,
                        group_size=1, seed=0, max_fail_events=1)
    sched = AsyncBufferScheduler(
        sys_, handles, model_bytes=1e5, compute_ms=compute, buffer_k=4,
        barrier=True, churn=churn,
    )
    events = sched.run(1, max_events=200_000)
    assert any(c.kind == "fail" and victim in c.nodes for c in sched.churn_log)
    ev0 = [e for e in events if e.app_id == handles[0].app_id]
    assert len(ev0) == 1 and ev0[0].arrivals == 4
    # every app-0 worker ran exactly one cycle — nobody lapped the barrier
    cycles = {w: sched._cycle.get((0, w), 0) for w in sorted(members0)}
    assert all(c == 1 for c in cycles.values()), cycles


def test_force_admit_drains_blocklist_and_run_completes_under_churn():
    """Satellite: when K exceeds the live non-blocklisted pool, forced
    admissions must drain the blocklist (not leave workers pinned) and
    the buffer keeps filling through heavy churn."""
    sel = UtilitySelector(
        deadline_ms=30.0, epsilon=0.0, admit_quantile=0.9,
        blocklist_after=1, blocklist_rounds=50, seed=0,
    )
    sys_, app = build_app(seed=22, workers=8)
    churn = ChurnModel(period_ms=150.0, downtime_ms=300.0, group_size=3, seed=1)
    res = rounds.run_async(
        sys_, [app], applies=10, buffer_k=6, staleness_alpha=0.5, model_bytes=1e5,
        compute_ms=async_engine.worker_compute_fn(40.0, 6.0, seed=1),
        churn=churn, selector=sel, adaptive=True,
        adaptive_kwargs={"k_min": 4, "target_staleness": 0.2, "gain": 1.0},
    )
    assert len(res["events"]) == 10
    forced = [st for st in sel._stats.values() if st.force_admits > 0]
    assert forced, "the liveness guard should have force-admitted someone"
    # the drain itself, unit-level: a forced admission zeroes the pending
    # block (misses survive, so a still-slow worker can re-earn it)
    st = sel._s(0, 10**9)
    st.block_offers, st.misses = 40, 3
    sel.on_force_admit(0, 10**9)
    assert st.block_offers == 0 and st.misses == 3 and st.force_admits == 1


def test_adaptive_k_rate_ema_survives_full_outage_gap():
    """Satellite: a commit gap longer than the apply interval (all
    workers failed, later rejoined) must not poison the arrival-rate
    EMA and clamp K at k_min forever."""
    def feed(ctrl):
        for i in range(20):
            ctrl.on_commit(10.0 * i)  # 0.1 commits/ms
        # full outage: no commits for 1e6 ms; the first post-rejoin commit
        # completes the buffer that was nearly full before the outage, so
        # the apply fires before the EMA sees any healthy inter-arrival
        ctrl.on_commit(1e6)
        return ctrl.on_apply(1e6 + 1.0, [1, 1, 1], live_workers=64)

    fixed = AdaptiveKController(
        k_init=8, k_min=1, target_staleness=1.0, gain=0.0,
        arrival_beta=0.9, max_apply_interval_ms=100.0,
    )
    k = feed(fixed)
    assert fixed.arrivals_per_ms == pytest.approx(0.1, rel=0.05)
    assert k == 8, f"K should hold across the outage, got {k}"
    # the old behavior (gap folded into the EMA) demonstrates the bug it
    # fixes: the rate collapses and the interval cap clamps K to k_min
    legacy = AdaptiveKController(
        k_init=8, k_min=1, target_staleness=1.0, gain=0.0,
        arrival_beta=0.9, max_apply_interval_ms=100.0, rate_gap_ms=1e18,
    )
    k_old = feed(legacy)
    assert legacy.arrivals_per_ms < 0.05 and k_old == 1
    # ... and with the fix, K keeps tracking once traffic resumes
    for i in range(1, 20):
        fixed.on_commit(1e6 + 10.0 * i)
    assert fixed.on_apply(1e6 + 200.0, [1, 1, 1], live_workers=64) == 8
    # persistent slowness is NOT forgiven: only the first long gap is an
    # outage; repeated long gaps fold and the interval cap pulls K down
    slow = AdaptiveKController(
        k_init=8, k_min=1, target_staleness=1.0, gain=0.0,
        arrival_beta=0.9, max_apply_interval_ms=100.0,
    )
    for i in range(10):
        slow.on_commit(1e5 * i)  # every gap >> the 100ms window
    assert slow.on_apply(1e6 + 1.0, [1, 1, 1], live_workers=64) == 1
    assert slow.arrivals_per_ms < 1e-3


# -- dirichlet min_samples + ragged masked padding (satellite) ----------------


def test_dirichlet_partition_low_alpha_zero_sample_repro_and_fix():
    y = np.random.default_rng(0).integers(0, 4, size=200).astype(np.int32)
    raw = data_mod.dirichlet_partition(y, 24, alpha=0.05, seed=3, min_samples=0)
    assert any(len(p) == 0 for p in raw), "low alpha should reproduce empty clients"
    fixed = data_mod.dirichlet_partition(y, 24, alpha=0.05, seed=3, min_samples=2)
    assert all(len(p) >= 2 for p in fixed)
    # a partition stays a partition: indices disjoint and complete
    allidx = np.concatenate(fixed)
    assert len(allidx) == len(y) and len(np.unique(allidx)) == len(y)
    # default guarantees >= 1
    dflt = data_mod.dirichlet_partition(y, 24, alpha=0.05, seed=3)
    assert all(len(p) >= 1 for p in dflt)
    # clients already above the floor are untouched by the default
    rich = data_mod.dirichlet_partition(y, 4, alpha=10.0, seed=5, min_samples=0)
    assert all(len(p) >= 1 for p in rich)
    same = data_mod.dirichlet_partition(y, 4, alpha=10.0, seed=5)
    for a, b in zip(rich, same):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError):
        data_mod.dirichlet_partition(y, 300, alpha=1.0, min_samples=1)


def test_masked_padding_matches_reference_on_heavily_ragged_shards(monkeypatch):
    """Engine equivalence where it hurts: shard sizes 1 vs ~200 in one
    padded stack — the vectorized masked path must reproduce each
    worker's unpadded loss and delta."""
    import jax

    sys_, app = build_app(seed=30, workers=6)
    ws = [w for w in sorted(app.handle.tree.members) if w in app.data]
    # make it brutally ragged: sizes 1, 2, 5, and the rest untouched
    for w, size in zip(ws[:3], (1, 2, 5)):
        x, y = app.data[w]
        app.data[w] = (x[:size], y[:size])
    masks = []
    orig = engine.megabatched_local_train

    def spy(params, x, y, mask, **kw):
        masks.append(np.asarray(mask))
        return orig(params, x, y, mask, **kw)

    monkeypatch.setattr(engine, "megabatched_local_train", spy)
    [vec] = engine.fused_local_training([(app, ws, None)])
    (mask,) = masks
    assert mask.shape[0] == engine.bucket_size(len(ws))
    np.testing.assert_allclose(
        mask.sum(axis=1),
        [len(app.data[w][1]) for w in ws] + [0] * (mask.shape[0] - len(ws)),
    )
    ref = reference_local_training(app, ws)
    assert vec[1] == ref[1]  # weights = shard sizes
    np.testing.assert_allclose(vec[2], ref[2], rtol=1e-4, atol=1e-6)
    for dv, dr in zip(vec[0], ref[0]):
        for lv, lr_ in zip(jax.tree.leaves(dv), jax.tree.leaves(dr)):
            np.testing.assert_allclose(
                np.asarray(lv), np.asarray(lr_), rtol=1e-4, atol=1e-6
            )
