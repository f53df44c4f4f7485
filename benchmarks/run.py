"""Benchmark driver: one module per paper table/figure.

``python -m benchmarks.run`` runs every registered bench and prints
``name,us_per_call,derived`` CSV rows.  ``--help`` lists the registry
with a one-line description per bench; ``--only NAME`` (repeatable)
restricts the run to named entries.
"""
from __future__ import annotations

import argparse
import importlib
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# (name, module, description) — every bench registers a real one-line
# description here, surfaced by --help without importing the module (a
# broken bench must not take down the driver's help or other benches).
REGISTRY: list[tuple[str, str, str]] = [
    ("engine+sim(TabIII)", "benchmarks.bench_engine",
     "M-app event simulator vs centralized baseline"),
    ("async_vs_sync(FedBuff)", "benchmarks.bench_async",
     "sync vs fixed-K vs adaptive-K vs adaptive-K+utility time-to-target-loss under churn"),
    ("fairness(TabIII)", "benchmarks.bench_fairness",
     "multi-app uplink fairness under weighted-fair re-pricing: Jain's index at M in {4,16,64}, every app reaches its target loss"),
    ("compression", "benchmarks.bench_compression",
     "compressed wire, both directions: qsgd-int8 commits (time-to-target + <=1e-2 loss-gap gates on a tight uplink) and delta-qsgd downlink broadcasts (total bytes < 0.35x, time-to-target <= 0.90x vs uplink-only)"),
    ("scale(perf)", "benchmarks.bench_scale",
     "million-node scale layer: route_many hops vs N log-fit (R^2 gate), cohort-batched events/s + peak RSS vs M, M=16 sampled(ht=0) trace-identity anchor"),
    ("scalability(Fig5)", "benchmarks.bench_scalability",
     "overlay join/route cost vs network size"),
    ("hops(Fig6)", "benchmarks.bench_hops",
     "dataflow-tree path lengths vs DHT routing bounds"),
    ("traffic(Fig7)", "benchmarks.bench_traffic",
     "per-round bytes on the tree vs flat aggregation"),
    ("time_to_accuracy(TabIII/Fig8-9)", "benchmarks.bench_time_to_accuracy",
     "FedAvg/FedProx rounds to target accuracy on non-IID shards"),
    ("adaptivity(Fig11-14)", "benchmarks.bench_adaptivity",
     "game-theoretic vs bandit vs OPT planner: cumulative latency, Nash regret, selection spread (gated ordering)"),
    ("placement(live)", "benchmarks.bench_placement",
     "live placement loop vs static trees: time-to-target-loss <= 0.95x and Jain no worse under >=10% churn, placement=None trace identity"),
    ("runtime(Fig15-16)", "benchmarks.bench_runtime",
     "end-to-end simulated round time across model sizes"),
    ("recovery(Fig17-18)", "benchmarks.bench_recovery",
     "master/worker failure repair latency and state-restore hit rate"),
    ("overhead(Fig19)", "benchmarks.bench_overhead",
     "control-plane overhead of the Table-II verbs"),
    ("kernels", "benchmarks.bench_kernels",
     "Pallas tree_aggregate / tree_aggregate_groups vs XLA reference"),
]


def _registry_help() -> str:
    width = max(len(n) for n, _, _ in REGISTRY)
    lines = ["registered benches:"]
    for name, _, desc in REGISTRY:
        lines.append(f"  {name:<{width}}  {desc}")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.run",
        description=__doc__,
        epilog=_registry_help(),
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument(
        "--only", action="append", default=None, metavar="NAME",
        help="run only the named bench (repeatable; names as listed below)",
    )
    args = ap.parse_args(argv)
    from repro.launch import compile_cache

    compile_cache.configure()
    selected = REGISTRY
    if args.only:
        known = {n for n, _, _ in REGISTRY}
        unknown = [n for n in args.only if n not in known]
        if unknown:
            ap.error(f"unknown bench name(s): {unknown}; known: {sorted(known)}")
        selected = [r for r in REGISTRY if r[0] in args.only]

    print("name,us_per_call,derived")
    failures = 0
    for label, mod_name, _ in selected:
        try:
            mod = importlib.import_module(mod_name)
            for line in mod.run():
                print(line, flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{label},NaN,FAILED", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
