"""Profile the simulator's hot paths: cProfile + jax.profiler harness.

Runs a canned bench_async-style configuration (M=16 apps by default,
heterogeneous compute, >=10% churn, real training in the loop) under
cProfile, prints the top-20 cumulative hot spots, and writes trace
artifacts:

- ``<out>/cprofile.pstats`` — the full cProfile dump
  (``python -m pstats`` or snakeviz to explore);
- ``<out>/jax-trace/`` — a ``jax.profiler`` trace (open in Perfetto /
  TensorBoard) covering the same run, so XLA compile vs execute time is
  attributable alongside the Python-side event engine.

Usage (see README "Profiling"):

    PYTHONPATH=src python tools/profile_sim.py                 # optimized paths
    PYTHONPATH=src python tools/profile_sim.py --baseline      # pre-optimization
    PYTHONPATH=src python tools/profile_sim.py --m 4 --applies 2 --top 30

This is how the hot-path PR's before/after map in docs/performance.md
was produced: ``--baseline`` selects the legacy engines (Pallas
interpret kernels, per-version dispatch, full-water-filling repricing)
so the two profiles are directly comparable.
"""
from __future__ import annotations

import argparse
import cProfile
import io
import os
import pstats
import resource
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def canned_fixture(*, m_apps: int, workers: int, seed: int):
    """The canned workload's system, apps and ``run_async`` arguments:
    600 nodes in 4 zones, ``m_apps`` apps of ``workers`` workers each,
    heterogeneous compute and >= 10% churn."""
    from benchmarks.bench_async import _make_apps
    from benchmarks.common import build_system
    from repro.core.sim import ChurnModel
    from repro.fl import async_engine

    base_ms, spread = 40.0, 6.0
    per_worker = async_engine.worker_compute_fn(base_ms, spread, seed=seed)
    sys_a, nodes_a, rng_a = build_system(n_nodes=600, zones=4, seed=seed)
    apps_a = _make_apps(sys_a, nodes_a, rng_a, m_apps, workers, tag="p")
    churn = ChurnModel(
        period_ms=6.0 * base_ms, downtime_ms=12.0 * base_ms,
        group_size=max(1, round(0.1 * workers)), seed=seed,
    )
    run_kwargs = dict(
        buffer_k=max(2, workers // 2), staleness_alpha=0.5, model_bytes=2e5,
        compute_ms=per_worker, churn=churn,
    )
    return sys_a, apps_a, run_kwargs


def canned_run(*, m_apps: int, applies: int, workers: int, seed: int,
               optimized: bool) -> dict:
    """The canned workload: identical to a bench_hotpath trained run."""
    from repro.fl import async_engine, engine
    from repro.kernels import ops as kops

    sys_a, apps_a, run_kwargs = canned_fixture(m_apps=m_apps, workers=workers, seed=seed)
    prev_mode = kops.set_kernel_mode("auto" if optimized else "pallas")
    prev_bucketing = engine.set_bucketing(optimized)
    try:
        return async_engine.run_async(
            sys_a, apps_a, applies=applies, megabatch=optimized,
            incremental=optimized, **run_kwargs,
        )
    finally:
        kops.set_kernel_mode(prev_mode)
        engine.set_bucketing(prev_bucketing)


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--m", type=int, default=16, help="concurrent apps (default 16)")
    ap.add_argument("--applies", type=int, default=3, help="buffered applies per app")
    ap.add_argument("--workers", type=int, default=8, help="workers per app")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--top", type=int, default=20, help="hot spots to print")
    ap.add_argument("--baseline", action="store_true",
                    help="profile the pre-optimization paths instead")
    ap.add_argument("--out", default="profile_artifacts",
                    help="artifact directory (pstats dump + jax trace)")
    ap.add_argument("--no-jax-trace", action="store_true",
                    help="skip the jax.profiler trace (cProfile only)")
    args = ap.parse_args()

    import jax

    from repro.launch import compile_cache

    compile_cache.configure()
    os.makedirs(args.out, exist_ok=True)
    trace_dir = os.path.join(args.out, "jax-trace")
    label = "baseline (pre-optimization)" if args.baseline else "optimized"
    print(f"profiling {label}: M={args.m}, applies={args.applies}, "
          f"workers={args.workers}, backend={jax.default_backend()}")

    prof = cProfile.Profile()
    t0 = time.perf_counter()
    if args.no_jax_trace:
        prof.enable()
        res = canned_run(m_apps=args.m, applies=args.applies,
                         workers=args.workers, seed=args.seed,
                         optimized=not args.baseline)
        prof.disable()
    else:
        with jax.profiler.trace(trace_dir):
            prof.enable()
            res = canned_run(m_apps=args.m, applies=args.applies,
                             workers=args.workers, seed=args.seed,
                             optimized=not args.baseline)
            prof.disable()
    wall = time.perf_counter() - t0

    stats_path = os.path.join(args.out, "cprofile.pstats")
    prof.dump_stats(stats_path)
    buf = io.StringIO()
    pstats.Stats(prof, stream=buf).sort_stats("cumulative").print_stats(args.top)
    print(buf.getvalue())
    print(f"wall-clock: {wall:.2f}s; applies completed: {len(res['events'])}; "
          f"churn events: {len(res['churn'])}")
    # scale-layer counters (docs/performance.md "scale layer"): event
    # throughput and the process peak-RSS high-water mark
    sched = res["scheduler"]
    peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    peak_mb = peak_kb / 1024.0 if sys.platform != "darwin" else peak_kb / 2**20
    print(f"events dispatched: {sched.events_dispatched} "
          f"({sched.events_dispatched / max(wall, 1e-9):.0f} events/s wall, "
          f"heap max {sched.heap_max}); peak RSS: {peak_mb:.0f} MB")
    # per-app wire split (docs/performance.md "compressed downlink"):
    # commit (uplink) vs broadcast (downlink) bytes as the scheduler
    # priced them — compression policies show up directly here
    ts = sched.transport_stats()
    print("per-app wire bytes (up / down):")
    for ai, (up, down) in enumerate(zip(ts["uplink_bytes"], ts["downlink_bytes"])):
        print(f"  app {ai}: {up / 1e6:8.2f} MB up  /  {down / 1e6:8.2f} MB down")
    print(f"  total: {sum(ts['uplink_bytes']) / 1e6:.2f} MB up / "
          f"{sum(ts['downlink_bytes']) / 1e6:.2f} MB down")
    print(f"wrote {stats_path}")
    if not args.no_jax_trace:
        print(f"wrote jax trace under {trace_dir} (open with Perfetto or "
              f"TensorBoard's profile plugin)")


if __name__ == "__main__":
    main()
