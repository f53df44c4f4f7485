"""Million-node scale bench: O(log N) routing + cohort-batched events.

The paper's headline scalability claim is O(log N) hops "with millions
of nodes" (§VI, Fig. 5-6 at smaller N).  This bench measures it
directly against the vectorized scale layer (docs/performance.md
"scale layer"):

- **hops vs N** for N in {1e3, 1e4, 1e5, 1e6} (smoke: up to 1e5): each
  overlay is bulk-built with ``join_many`` and a fixed sample of routes
  is resolved through the batched ``route_many``; mean delivered hops
  are least-squares fit to ``hops = a + c*log2(N)`` and the fit must
  explain the curve (R^2 >= 0.95).  A random sub-sample of every batch
  is replayed through the scalar object-API ``route`` (the oracle) and
  must match hop-for-hop.
- **events/s + peak RSS vs M** for M in {4, 16, 64, 256} (smoke: up to
  64): pure timing-model runs (no trainer) of the cohort-batched
  scheduler in sampled-congestion mode — the configuration that holds
  the heap at O(apps + uplinks).  Peak RSS is ``resource.getrusage``'s
  high-water mark, so the sweep runs small M -> large M and each row
  reports the peak *up to and including* that M.
- **forest bootstrap vs N** for the same N ladder: subscribe N workers
  split across M apps through ``join_many`` + ``subscribe_many`` (the
  vectorized union-of-paths graft) and report subscribes/s, tree
  depth, and peak RSS.  At N <= 1e4 the bulk trees must be
  node-for-node identical to a sequential ``subscribe`` loop (the
  oracle — parent maps, children order, members, schedules), at
  N = 1e5 bulk bootstrap must be >= 10x faster than the loop, and mean
  member depth must fit ``a + c*log2(N)`` with R^2 >= 0.95.
- **M=16 exactness anchor**: ``congestion_mode="sampled"`` with
  ``hot_threshold=0`` must degenerate to the exact trace
  (ApplyEvent/ChurnRecord dataclass equality, exact float timestamps).
  The cohort-batched heap's identity to one heap entry per event is a
  test (tests/test_scale.py).
- **sampled-congestion error**: apply-time relative error of sampled
  mode vs the exact trace, with and without periodic cold-cycle
  re-pricing (``resample_every``) — reported, not gated (the knob
  trades exactness for events, the error bound is the datum).

Gates (CI fails on regression): hops and depth log-fit R^2 >= 0.95,
zero oracle mismatches, bulk-vs-sequential tree identity, >= 10x
bootstrap speedup at N=1e5, the sampled(ht=0) trace identity.
``--max-events`` threads the event budget through for longer runs (the
budget error names it).

``python -m benchmarks.bench_scale --smoke`` writes BENCH_scale.json
(the CI artifact).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import resource
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from benchmarks.common import build_system, row

FULL_NS = (1_000, 10_000, 100_000, 1_000_000)
SMOKE_NS = (1_000, 10_000, 100_000)
FULL_MS = (4, 16, 64, 256)
SMOKE_MS = (4, 16, 64)


def _peak_rss_mb() -> float:
    """ru_maxrss is KiB on Linux, bytes on macOS."""
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    if sys.platform == "darwin":
        peak /= 1024
    return peak / 1024.0


# -- hops vs N (route_many against the scalar oracle) -------------------------


def route_scaling(ns, *, zones=8, routes=2000, parity_sample=50, seed=0) -> list[dict]:
    from repro.core.nodeid import IdSpace
    from repro.core.overlay import MultiRingOverlay

    out = []
    for n in ns:
        space = IdSpace(zone_bits=int(math.log2(zones)), suffix_bits=28)
        ov = MultiRingOverlay(space, base_bits=4, seed=seed)
        rng = np.random.default_rng(seed + n)
        t0 = time.perf_counter()
        ids = ov.join_many(
            rng.integers(0, zones, n), coords=rng.uniform(0, 1000, (n, 2))
        )
        build_s = time.perf_counter() - t0
        srcs = ids[rng.integers(0, n, routes)]
        keys = rng.integers(0, 1 << space.total_bits, routes)
        t0 = time.perf_counter()
        batch = ov.route_many(srcs, keys)
        route_s = time.perf_counter() - t0
        mismatches = 0
        for k in rng.integers(0, routes, parity_sample):
            k = int(k)
            res = ov.route(int(srcs[k]), int(keys[k]))
            if (
                res.path != batch.path(k)
                or res.hops != int(batch.hops[k])
                or res.blocked != bool(batch.blocked[k])
            ):
                mismatches += 1
        delivered = ~batch.blocked
        out.append(
            {
                "n": int(n),
                "mean_hops": float(batch.hops[delivered].mean()),
                "max_hops": int(batch.hops[delivered].max()),
                "routes": int(routes),
                "build_s": build_s,
                "routes_per_sec": routes / max(route_s, 1e-9),
                "oracle_mismatches": mismatches,
                "peak_rss_mb": _peak_rss_mb(),
            }
        )
    return out


def log_fit(curve: list[dict], key: str = "mean_hops") -> dict:
    """Least-squares y = a + c*log2(N) over ``curve[i][key]``; returns
    slope, intercept, R^2."""
    x = np.log2([r["n"] for r in curve])
    y = np.array([r[key] for r in curve])
    c, a = np.polyfit(x, y, 1)
    pred = a + c * x
    ss_res = float(((y - pred) ** 2).sum())
    ss_tot = float(((y - y.mean()) ** 2).sum())
    r2 = 1.0 - ss_res / max(ss_tot, 1e-12)
    return {"slope_per_log2n": float(c), "intercept": float(a), "r2": float(r2)}


# -- forest bootstrap vs N (subscribe_many against the sequential oracle) -----


def _tree_fingerprint(tree) -> tuple:
    """Everything the bulk graft must reproduce node-for-node: topology,
    child order, membership, and the schedules derived from them."""
    return (
        tree.root,
        sorted(tree.parent.items()),
        [(p, list(tree.children[p])) for p in tree.children],
        sorted(tree.members),
        tree.aggregation_schedule(),
    )


def forest_bootstrap(ns, *, m_apps=4, zones=8, seed=0, oracle_max=10_000,
                     speedup_at=100_000) -> list[dict]:
    """Subscribe N workers across M apps, bulk vs sequential.

    The sequential ``subscribe`` loop runs (on a second Forest over the
    SAME overlay, so routes are identical) wherever it is affordable:
    at every N <= ``oracle_max`` it is the identity oracle, and at
    N == ``speedup_at`` it is the timing baseline for the >= 10x gate.
    """
    from repro.core.forest import Forest
    from repro.core.nodeid import IdSpace
    from repro.core.overlay import MultiRingOverlay

    out = []
    for n in ns:
        space = IdSpace(zone_bits=int(math.log2(zones)), suffix_bits=28)
        ov = MultiRingOverlay(space, base_bits=4, seed=seed)
        rng = np.random.default_rng(seed + n)
        ids = ov.join_many(
            rng.integers(0, zones, n), coords=rng.uniform(0, 1000, (n, 2))
        )
        shards = np.array_split(rng.permutation(ids), m_apps)

        def bulk_build():
            bulk = Forest(ov)
            trees = [bulk.create_tree(f"boot-{n}-{a}") for a in range(m_apps)]
            t0 = time.perf_counter()
            for t, shard in zip(trees, shards):
                bulk.subscribe_many(t.app_id, shard)
            return time.perf_counter() - t0, trees

        # best-of-2: the graft is deterministic, so the rebuild only
        # de-noises the wall clock (allocator churn from earlier axes)
        s1, trees = bulk_build()
        s2, trees = bulk_build()
        bulk_s = min(s1, s2)
        depths = np.concatenate(
            [t.depths_of(np.asarray(sorted(t.members), np.int64)) for t in trees]
        )
        rec = {
            "n": int(n),
            "m_apps": int(m_apps),
            "mean_depth": float(depths.mean()),
            "max_depth": int(depths.max()),
            "bulk_s": bulk_s,
            "subscribes_per_sec": n / max(bulk_s, 1e-9),
            "peak_rss_mb": _peak_rss_mb(),
        }
        if n <= oracle_max or n == speedup_at:
            seq = Forest(ov)
            seq_trees = [seq.create_tree(f"boot-{n}-{a}") for a in range(m_apps)]
            t0 = time.perf_counter()
            for t, shard in zip(seq_trees, shards):
                for w in shard.tolist():
                    seq.subscribe(t.app_id, int(w))
            rec["seq_s"] = time.perf_counter() - t0
            rec["speedup"] = rec["seq_s"] / max(bulk_s, 1e-9)
            if n <= oracle_max:
                rec["identical"] = all(
                    _tree_fingerprint(tb) == _tree_fingerprint(ts)
                    for tb, ts in zip(trees, seq_trees)
                )
        out.append(rec)
    return out


# -- events/s + RSS vs M (cohort-batched timing model) ------------------------


def _make_handles(sys_, nodes, rng, m, w, tag=""):
    """Timing-model app handles: trees + subscriptions, no jax models."""
    handles = []
    for a in range(m):
        h = sys_.CreateTree(f"scale{tag}-{m}-{a}")
        for node in rng.choice(nodes, size=w, replace=False):
            sys_.Subscribe(h.app_id, int(node))
        handles.append(h)
    return handles


def _timing_run(m_apps, *, congestion_mode, hot_threshold=4, workers=8,
                applies=2, seed=0, base_ms=40.0, spread=6.0, model_bytes=2e5,
                n_nodes=600, zones=4, max_events=1_000_000,
                resample_every=None, resample_events=None) -> dict:
    from repro.core.sim import AsyncBufferScheduler, ChurnModel
    from repro.fl import async_engine

    per_worker = async_engine.worker_compute_fn(base_ms, spread, seed=seed)
    sys_a, nodes_a, rng_a = build_system(n_nodes=n_nodes, zones=zones, seed=seed)
    handles = _make_handles(sys_a, nodes_a, rng_a, m_apps, workers, tag="s")
    churn = ChurnModel(
        period_ms=6.0 * base_ms, downtime_ms=12.0 * base_ms,
        group_size=max(1, round(0.1 * workers)), seed=seed,
    )
    sched = AsyncBufferScheduler(
        sys_a, handles, model_bytes=model_bytes, compute_ms=per_worker,
        buffer_k=max(2, workers // 2), churn=churn,
        congestion_mode=congestion_mode, hot_threshold=hot_threshold,
        resample_every=resample_every, resample_events=resample_events,
    )
    t0 = time.perf_counter()
    events = sched.run(applies, max_events=max_events)
    wall = time.perf_counter() - t0
    return {
        "events": events,
        "churn": list(sched.churn_log),
        "wall_s": wall,
        "events_dispatched": sched.events_dispatched,
        "events_per_sec": sched.events_dispatched / max(wall, 1e-9),
        "heap_max": sched.heap_max,
        "resamples": sched._resample_count,
    }


def event_scaling(ms, *, applies=2, seed=0, max_events=1_000_000) -> list[dict]:
    """Sweep M small -> large (getrusage is a high-water mark)."""
    out = []
    for m in ms:
        r = _timing_run(
            m, congestion_mode="sampled", applies=applies,
            seed=seed, max_events=max_events,
        )
        out.append(
            {
                "m": int(m),
                "applies_completed": len(r["events"]),
                "events_dispatched": r["events_dispatched"],
                "events_per_sec": r["events_per_sec"],
                "heap_max": r["heap_max"],
                "wall_s": r["wall_s"],
                "peak_rss_mb": _peak_rss_mb(),
            }
        )
    return out


def trace_identity(*, m_apps=16, applies=3, seed=0, max_events=1_000_000) -> dict:
    """The exactness anchor: sampled(ht=0) vs exact."""
    kw = dict(applies=applies, seed=seed, max_events=max_events)
    exact = _timing_run(m_apps, congestion_mode="exact", **kw)
    deg = _timing_run(m_apps, congestion_mode="sampled", hot_threshold=0, **kw)
    return {
        "m": int(m_apps),
        "sampled_ht0_identical": exact["events"] == deg["events"]
        and exact["churn"] == deg["churn"],
        "events_dispatched": exact["events_dispatched"],
        "heap_max": exact["heap_max"],
    }


def sampled_error(*, m_apps=8, applies=2, seed=1, base_ms=40.0,
                  max_events=1_000_000) -> dict:
    """Apply-time error of sampled congestion vs the exact trace, with
    and without periodic cold-cycle re-pricing.  Per (app, apply_index)
    relative |t_sampled - t_exact| / t_exact; the refresh bounds drift
    under bursty contention (ROADMAP follow-on (c)) — reported as data,
    not gated."""
    kw = dict(applies=applies, seed=seed, max_events=max_events)
    exact = _timing_run(m_apps, congestion_mode="exact", **kw)
    runs = {
        "sampled": _timing_run(
            m_apps, congestion_mode="sampled", **kw
        ),
        "sampled_resampled": _timing_run(
            m_apps, congestion_mode="sampled",
            resample_every=2.0 * base_ms, **kw
        ),
    }
    ref = {(e.app_id, e.apply_index): e.time_ms for e in exact["events"]}
    out = {"m": int(m_apps), "applies_per_app": int(applies)}
    for tag, r in runs.items():
        errs = [
            abs(e.time_ms - ref[(e.app_id, e.apply_index)])
            / max(ref[(e.app_id, e.apply_index)], 1e-9)
            for e in r["events"]
            if (e.app_id, e.apply_index) in ref
        ]
        out[tag] = {
            "mean_rel_err": float(np.mean(errs)) if errs else 0.0,
            "max_rel_err": float(np.max(errs)) if errs else 0.0,
            "events_dispatched": r["events_dispatched"],
            "resamples": r["resamples"],
        }
    out["exact_events_dispatched"] = exact["events_dispatched"]
    return out


# -- gates / drivers ----------------------------------------------------------


def gate(payload: dict, *, min_r2: float = 0.95, min_speedup: float = 10.0) -> list[str]:
    """The acceptance gates; returns failure messages (empty = pass)."""
    fails = []
    fit = payload["hops_fit"]
    if fit["r2"] < min_r2:
        fails.append(
            f"hops-vs-N log fit R^2 {fit['r2']:.4f} below the {min_r2} gate"
        )
    for r in payload["hops_vs_n"]:
        if r["oracle_mismatches"]:
            fails.append(
                f"N={r['n']}: {r['oracle_mismatches']} route_many results "
                "diverge from the scalar oracle"
            )
    dfit = payload["depth_fit"]
    if dfit["r2"] < min_r2:
        fails.append(
            f"depth-vs-N log fit R^2 {dfit['r2']:.4f} below the {min_r2} gate"
        )
    for r in payload["forest_vs_n"]:
        if "identical" in r and not r["identical"]:
            fails.append(
                f"N={r['n']}: subscribe_many tree diverges from the "
                "sequential-subscribe oracle"
            )
        if "speedup" in r and r["n"] >= 100_000 and r["speedup"] < min_speedup:
            fails.append(
                f"N={r['n']}: bulk bootstrap speedup {r['speedup']:.1f}x "
                f"below the {min_speedup}x gate"
            )
    tid = payload["trace_identity"]
    if not tid["sampled_ht0_identical"]:
        fails.append("M=16 sampled(hot_threshold=0) trace diverges from exact")
    for r in payload["events_vs_m"]:
        want = r["m"] * payload["applies_per_app"]
        if r["applies_completed"] < want:
            fails.append(
                f"M={r['m']}: only {r['applies_completed']}/{want} applies completed"
            )
    return fails


def bench(*, smoke: bool, max_events: int, seed: int = 0) -> dict:
    ns = SMOKE_NS if smoke else FULL_NS
    ms = SMOKE_MS if smoke else FULL_MS
    applies = 2
    curve = route_scaling(ns, seed=seed)
    fit = log_fit(curve)
    forest = forest_bootstrap(ns, seed=seed)
    dfit = log_fit(forest, key="mean_depth")
    tid = trace_identity(seed=seed, max_events=max_events)
    sweep = event_scaling(ms, applies=applies, seed=seed, max_events=max_events)
    serr = sampled_error(seed=seed + 1, max_events=max_events)
    return {
        "bench": "scale_vectorized_overlay_cohort_events",
        "smoke": bool(smoke),
        "applies_per_app": applies,
        "hops_vs_n": curve,
        "hops_fit": fit,
        "forest_vs_n": forest,
        "depth_fit": dfit,
        "trace_identity": tid,
        "events_vs_m": sweep,
        "sampled_error": serr,
    }


def run() -> list[str]:
    """Registry entry (python -m benchmarks.run): smoke-sized."""
    payload = bench(smoke=True, max_events=1_000_000)
    out = []
    for r in payload["hops_vs_n"]:
        out.append(
            row(
                f"scale_route_n{r['n']}",
                1e6 / max(r["routes_per_sec"], 1e-9),
                f"mean_hops={r['mean_hops']:.2f};"
                f"oracle_mismatches={r['oracle_mismatches']}",
            )
        )
    for r in payload["forest_vs_n"]:
        out.append(
            row(
                f"scale_forest_n{r['n']}",
                1e6 / max(r["subscribes_per_sec"], 1e-9),
                f"mean_depth={r['mean_depth']:.2f};"
                f"identical={r.get('identical', 'n/a')};"
                f"speedup={r.get('speedup', float('nan')):.1f}",
            )
        )
    fit = payload["hops_fit"]
    dfit = payload["depth_fit"]
    tid = payload["trace_identity"]
    serr = payload["sampled_error"]
    for r in payload["events_vs_m"]:
        out.append(
            row(
                f"scale_events_m{r['m']}",
                r["wall_s"] * 1e6,
                f"events_per_sec={r['events_per_sec']:.0f};"
                f"heap_max={r['heap_max']};peak_rss_mb={r['peak_rss_mb']:.0f}",
            )
        )
    out.append(
        row(
            "scale_gates",
            0.0,
            f"fit_r2={fit['r2']:.4f};slope={fit['slope_per_log2n']:.3f};"
            f"depth_fit_r2={dfit['r2']:.4f};"
            f"sampled_ht0_identical={tid['sampled_ht0_identical']};"
            f"resample_mean_err={serr['sampled_resampled']['mean_rel_err']:.4f}",
        )
    )
    return out


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--smoke", action="store_true",
                    help="N <= 1e5, M <= 64 (CI tier); same gates")
    ap.add_argument("--out", default="BENCH_scale.json")
    ap.add_argument("--max-events", type=int, default=1_000_000,
                    help="event budget per scheduler run (threaded through)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    payload = bench(smoke=args.smoke, max_events=args.max_events, seed=args.seed)
    out_path = os.path.abspath(args.out)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, allow_nan=False)

    for r in payload["hops_vs_n"]:
        print(
            f"N={r['n']:>9,}: mean hops {r['mean_hops']:.2f} (max {r['max_hops']}), "
            f"build {r['build_s']:.2f}s, {r['routes_per_sec']:.0f} routes/s, "
            f"oracle mismatches {r['oracle_mismatches']}, "
            f"peak RSS {r['peak_rss_mb']:.0f} MB"
        )
    fit = payload["hops_fit"]
    print(
        f"log fit: hops = {fit['intercept']:.2f} + "
        f"{fit['slope_per_log2n']:.3f}*log2(N), R^2 = {fit['r2']:.4f}"
    )
    for r in payload["forest_vs_n"]:
        extra = ""
        if "speedup" in r:
            extra += f", {r['speedup']:.1f}x vs sequential"
        if "identical" in r:
            extra += f", identical={r['identical']}"
        print(
            f"forest N={r['n']:>9,}: {r['subscribes_per_sec']:.0f} subscribes/s, "
            f"mean depth {r['mean_depth']:.2f} (max {r['max_depth']}), "
            f"bulk {r['bulk_s']:.2f}s{extra}, peak RSS {r['peak_rss_mb']:.0f} MB"
        )
    dfit = payload["depth_fit"]
    print(
        f"depth fit: depth = {dfit['intercept']:.2f} + "
        f"{dfit['slope_per_log2n']:.3f}*log2(N), R^2 = {dfit['r2']:.4f}"
    )
    serr = payload["sampled_error"]
    print(
        f"sampled apply-time error vs exact (M={serr['m']}): "
        f"frozen mean {serr['sampled']['mean_rel_err']:.4f} "
        f"(max {serr['sampled']['max_rel_err']:.4f}); with resample "
        f"mean {serr['sampled_resampled']['mean_rel_err']:.4f} "
        f"(max {serr['sampled_resampled']['max_rel_err']:.4f}, "
        f"{serr['sampled_resampled']['resamples']} resamples)"
    )
    tid = payload["trace_identity"]
    print(
        f"M={tid['m']} trace identity: sampled(ht=0) == exact: "
        f"{tid['sampled_ht0_identical']}; heap max {tid['heap_max']}"
    )
    for r in payload["events_vs_m"]:
        print(
            f"M={r['m']:>4}: {r['events_per_sec']:.0f} events/s, "
            f"{r['applies_completed']} applies, heap max {r['heap_max']}, "
            f"wall {r['wall_s']:.2f}s, peak RSS {r['peak_rss_mb']:.0f} MB"
        )
    fails = gate(payload)
    print(f"wrote {out_path}")
    for msg in fails:
        print(f"GATE FAIL: {msg}")
    if fails:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
