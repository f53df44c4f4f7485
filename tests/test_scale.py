"""Scale layer: vectorized routing parity, cohort trace identity,
sampled congestion invariants, and the enriched event-budget error.

- ``route_many`` must match the scalar object-API ``route`` (the
  oracle) hop-for-hop — path, hop count, blocked flag, and path
  latency — on random overlays with churn (hypothesis property).
- ``neighborhood_set`` (spatial-grid index) must equal the brute-force
  full-sort result.
- The cohort-batched scheduler in exact mode must reproduce the
  per-event oracle's trace (tests/oracles.py) byte-for-byte (exact
  ApplyEvent/ChurnRecord equality) at M=16, and
  ``congestion_mode="sampled"`` with ``hot_threshold=0`` must
  degenerate to the exact trace.
- ``EventCore.run_events`` budget exhaustion must name the clock, heap
  occupancy, per-app progress, and the ``max_events`` knob.
"""
import math

import numpy as np
import pytest

try:  # optional dev dep: the property tests widen to random draws with it
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.nodeid import IdSpace
from repro.core.overlay import MultiRingOverlay

ZONES = 4


def build_overlay(n, seed, churn_frac=0.0):
    space = IdSpace(zone_bits=int(math.log2(ZONES)), suffix_bits=20)
    ov = MultiRingOverlay(space, base_bits=4, seed=seed)
    rng = np.random.default_rng(seed)
    ids = ov.join_many(
        rng.integers(0, ZONES, n), coords=rng.uniform(0, 100, (n, 2))
    )
    if churn_frac > 0:
        for nid in rng.choice(ids, size=int(churn_frac * n), replace=False):
            ov.fail(int(nid))
    return ov, rng


# -- route_many vs the scalar oracle ------------------------------------------


def _check_route_parity(seed, n, churn_frac):
    ov, rng = build_overlay(n, seed, churn_frac)
    nodes = ov.node_array()
    k = 40
    srcs = nodes[rng.integers(0, len(nodes), k)]
    keys = rng.integers(0, 1 << ov.space.total_bits, k)
    batch = ov.route_many(srcs, keys)
    for i in range(k):
        res = ov.route(int(srcs[i]), int(keys[i]))
        assert batch.path(i) == res.path, (i, batch.path(i), res.path)
        assert int(batch.hops[i]) == res.hops
        assert bool(batch.blocked[i]) == res.blocked
        assert batch.latency_ms[i] == pytest.approx(
            ov.path_latency(res.path), rel=1e-9
        )


@pytest.mark.parametrize("seed,n,churn_frac", [
    (0, 50, 0.0), (1, 200, 0.1), (2, 600, 0.25), (3, 333, 0.1),
])
def test_route_many_matches_scalar_oracle(seed, n, churn_frac):
    _check_route_parity(seed, n, churn_frac)


if HAVE_HYPOTHESIS:

    @settings(max_examples=12, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        n=st.integers(50, 600),
        churn_frac=st.sampled_from([0.0, 0.1, 0.25]),
    )
    def test_route_many_matches_scalar_oracle_property(seed, n, churn_frac):
        _check_route_parity(seed, n, churn_frac)


def test_route_many_restricted_zone_matches_oracle():
    ov, rng = build_overlay(400, seed=7, churn_frac=0.1)
    nodes = ov.node_array()
    srcs = nodes[rng.integers(0, len(nodes), 60)]
    keys = rng.integers(0, 1 << ov.space.total_bits, 60)
    zone = int(ov.space.zone_of(int(srcs[0])))
    batch = ov.route_many(srcs, keys, restrict_zone=zone)
    for i in range(60):
        res = ov.route(int(srcs[i]), int(keys[i]), restrict_zone=zone)
        assert batch.path(i) == res.path
        assert bool(batch.blocked[i]) == res.blocked


# -- neighborhood grid index vs brute force -----------------------------------


def _check_neighborhood_parity(seed, n, queries=25):
    ov, rng = build_overlay(n, seed, churn_frac=0.1)
    nodes = ov.node_array()
    for nid in nodes[rng.integers(0, len(nodes), queries)]:
        nid = int(nid)
        assert ov.neighborhood_set(nid) == ov.neighborhood_set_bruteforce(nid)


@pytest.mark.parametrize("seed,n", [(0, 30), (1, 120), (2, 500)])
def test_neighborhood_grid_matches_bruteforce(seed, n):
    _check_neighborhood_parity(seed, n)


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(30, 500))
    def test_neighborhood_grid_matches_bruteforce_property(seed, n):
        _check_neighborhood_parity(seed, n)


def test_neighborhood_grid_tracks_join_leave():
    ov, rng = build_overlay(200, seed=3)
    node = int(ov.node_array()[0])
    before = ov.neighborhood_set(node)
    # join a node right on top of the query point: must displace the set
    cx, cy = ov.coords[node]
    new = ov.join_random(0, coord=np.array([cx + 1e-6, cy + 1e-6]))
    assert new in ov.neighborhood_set(node)
    ov.fail(new)
    assert ov.neighborhood_set(node) == before


# -- cohort-batched scheduler: trace identity ---------------------------------


def _timing_run(m_apps, **kw):
    from benchmarks.bench_scale import _timing_run

    return _timing_run(m_apps, **kw)


def test_m16_cohort_trace_identical_to_per_event_baseline(monkeypatch):
    from oracles import PerEventScheduler

    from repro.core import sim

    kw = dict(applies=2, seed=0)
    coh = _timing_run(16, congestion_mode="exact", **kw)
    monkeypatch.setattr(sim, "AsyncBufferScheduler", PerEventScheduler)
    base = _timing_run(16, congestion_mode="exact", **kw)
    assert base["events"] == coh["events"]  # exact ApplyEvent equality
    assert base["churn"] == coh["churn"]  # exact ChurnRecord equality
    assert base["events_dispatched"] == coh["events_dispatched"]
    # the cohort heap is strictly smaller: one entry per app cohort
    assert coh["heap_max"] <= base["heap_max"]


def test_sampled_hot_threshold_zero_degenerates_to_exact():
    kw = dict(applies=2, seed=1)
    base = _timing_run(8, congestion_mode="exact", **kw)
    deg = _timing_run(
        8, congestion_mode="sampled", hot_threshold=0, **kw
    )
    assert base["events"] == deg["events"]
    assert base["churn"] == deg["churn"]


def test_sampled_mode_completes_with_fewer_events():
    kw = dict(applies=2, seed=0)
    exact = _timing_run(8, congestion_mode="exact", **kw)
    samp = _timing_run(8, congestion_mode="sampled", **kw)
    assert len(samp["events"]) == len(exact["events"])  # same applies done
    assert samp["events_dispatched"] < exact["events_dispatched"]


def test_congestion_mode_validated():
    from benchmarks.common import build_system
    from repro.core.sim import AsyncBufferScheduler

    sys_a, nodes_a, rng_a = build_system(n_nodes=50, zones=4, seed=0)
    h = sys_a.CreateTree("cm-check")
    sys_a.Subscribe(h.app_id, int(nodes_a[0]))
    with pytest.raises(ValueError, match="congestion_mode"):
        AsyncBufferScheduler(
            sys_a, [h], model_bytes=1e5, congestion_mode="statistical"
        )


# -- enriched event-budget diagnostic -----------------------------------------


def test_run_events_budget_error_names_progress():
    with pytest.raises(RuntimeError) as ei:
        _timing_run(4, congestion_mode="exact", applies=50,
                    seed=0, max_events=200)
    msg = str(ei.value)
    assert "event budget exhausted" in msg
    assert "200 events dispatched" in msg
    assert "clock=" in msg
    assert "live" in msg and "dead" in msg  # heap occupancy
    assert "apps done" in msg and "app0=" in msg  # per-app progress
    assert "max_events" in msg  # points at the knob to raise


def test_bench_scale_registered():
    from benchmarks.run import REGISTRY

    names = [n for n, _, _ in REGISTRY]
    assert "scale(perf)" in names
    mods = [m for _, m, _ in REGISTRY]
    assert "benchmarks.bench_scale" in mods


def test_log_fit_gate_math():
    from benchmarks.bench_scale import log_fit

    curve = [
        {"n": 10 ** e, "mean_hops": 1.0 + 0.25 * math.log2(10 ** e)}
        for e in (3, 4, 5)
    ]
    fit = log_fit(curve)
    assert fit["r2"] > 0.999
    assert fit["slope_per_log2n"] == pytest.approx(0.25, rel=1e-6)


# -- sampled-load resampling knob ---------------------------------------------


def test_resample_requires_sampled_mode_and_positive_values():
    from benchmarks.common import build_system
    from repro.core.sim import AsyncBufferScheduler

    sys_a, nodes_a, _ = build_system(n_nodes=50, zones=4, seed=0)
    h = sys_a.CreateTree("rs-check")
    sys_a.Subscribe(h.app_id, int(nodes_a[0]))
    with pytest.raises(ValueError, match="sampled"):
        AsyncBufferScheduler(
            sys_a, [h], model_bytes=1e5, congestion_mode="exact",
            resample_every=10.0,
        )
    with pytest.raises(ValueError, match="sampled"):
        AsyncBufferScheduler(
            sys_a, [h], model_bytes=1e5, congestion_mode="exact",
            resample_events=100,
        )
    for bad in ({"resample_every": 0.0}, {"resample_events": -5}):
        with pytest.raises(ValueError, match="must be > 0"):
            AsyncBufferScheduler(
                sys_a, [h], model_bytes=1e5, congestion_mode="sampled", **bad
            )


def test_resample_with_hot_threshold_zero_stays_exact():
    """With hot_threshold=0 every cycle is hot (exact), no cold spans
    exist, and the resample timer must be a pure no-op on the trace."""
    kw = dict(applies=2, seed=1)
    base = _timing_run(8, congestion_mode="exact", **kw)
    deg = _timing_run(
        8, congestion_mode="sampled", hot_threshold=0,
        resample_every=25.0, **kw
    )
    assert base["events"] == deg["events"]
    assert base["churn"] == deg["churn"]


def test_resample_timer_fires_and_run_completes():
    kw = dict(applies=2, seed=0)
    frozen = _timing_run(8, congestion_mode="sampled", **kw)
    res = _timing_run(
        8, congestion_mode="sampled", resample_every=40.0, **kw
    )
    assert len(res["events"]) == len(frozen["events"])  # same applies done
    assert res["resamples"] > 0
    assert frozen["resamples"] == 0


def test_resample_event_count_variant():
    kw = dict(applies=2, seed=0)
    res = _timing_run(
        8, congestion_mode="sampled", resample_events=500, **kw
    )
    assert res["resamples"] > 0


# -- forest bootstrap bench gates ---------------------------------------------


def test_forest_bootstrap_identity_and_gate_math():
    from benchmarks.bench_scale import forest_bootstrap, gate, log_fit

    rows = forest_bootstrap([300, 600], m_apps=2, zones=4, seed=0,
                            oracle_max=600, speedup_at=600)
    assert all(r["identical"] for r in rows)
    assert all(r["subscribes_per_sec"] > 0 for r in rows)
    # gate() passes a clean payload and flags a broken identity/speedup
    hops_curve = [
        {"n": 10 ** e, "mean_hops": 1.0 + 0.25 * math.log2(10 ** e),
         "oracle_mismatches": 0}
        for e in (3, 4, 5)
    ]
    depth_curve = [
        {"n": 10 ** e, "mean_depth": 0.8 + 0.24 * math.log2(10 ** e),
         "identical": True, "speedup": 12.0}
        for e in (3, 4, 5)
    ]
    payload = {
        "hops_vs_n": hops_curve,
        "hops_fit": log_fit(hops_curve),
        "forest_vs_n": depth_curve,
        "depth_fit": log_fit(depth_curve, key="mean_depth"),
        "trace_identity": {"sampled_ht0_identical": True},
        "events_vs_m": [],
        "applies_per_app": 2,
    }
    assert gate(payload) == []
    payload["forest_vs_n"][1]["identical"] = False
    assert any("oracle" in f for f in gate(payload))
    payload["forest_vs_n"][1]["identical"] = True
    payload["forest_vs_n"][2]["speedup"] = 1.5  # n=1e5 row: below the gate
    assert any("speedup" in f for f in gate(payload))


def test_paths_flat_matches_per_route_paths():
    ov, rng = build_overlay(300, seed=5, churn_frac=0.1)
    nodes = ov.node_array()
    srcs = nodes[rng.integers(0, len(nodes), 30)]
    keys = rng.integers(0, 1 << ov.space.total_bits, 30)
    batch = ov.route_many(srcs, keys)
    flat, offsets = batch.paths_flat()
    for i in range(30):
        assert flat[offsets[i]:offsets[i + 1]].tolist() == batch.path(i)
