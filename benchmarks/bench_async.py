"""Sync vs async buffered execution: time-to-target-loss under churn.

The async claim (ROADMAP / paper §VII): a barrier round is priced by its
slowest worker, so under a heterogeneous edge compute distribution the
synchronous engine crawls at straggler speed, while FedBuff-style
buffered aggregation applies after the K fastest arrivals and keeps the
pipeline full — even with ≥10% of workers failing and rejoining
mid-round (churn on the event clock, repaired by ``core/recovery``).

This bench runs four schedulers per M ∈ {1, 4, 16} concurrent apps:

- ``sync``    — barrier rounds, clean (no churn handicap);
- ``fixed``   — async, fixed K = W/2, heterogeneous compute + churn;
- ``adaptive``— same, but an ``AdaptiveKController`` re-sizes K each
  apply from the arrival rate + staleness percentile;
- ``adaptive+utility`` — adaptive K plus Oort-style utility client
  selection (``fl/selection.UtilitySelector``): chronic stragglers are
  parked, fast informative clients keep the buffer full.

All async variants share seeds, topology, shards and churn schedule, so
the comparison isolates the control policy.  Reported metric: simulated
time until the mean local loss first reaches the target, per app.

``python -m benchmarks.bench_async --smoke`` runs a small configuration
and writes a ``BENCH_async.json`` artifact (the CI perf trajectory).
The smoke run also gates the multi-app fairness acceptance criteria
(``benchmarks/bench_fairness.py``): at M = 16 with one hot app, Jain's
index over demand-normalized per-app uplink throughput must reach 0.8
under the weighted-fair engine, and every app must reach the target
loss.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from benchmarks.common import build_system, row


def _make_apps(sys_, nodes, rng, m, w, *, dim=16, classes=4, shard=24, tag=""):
    from repro import data as data_mod
    from repro.fl import rounds

    apps = []
    for a in range(m):
        x, y = data_mod.synthetic_classification(w * shard, dim, classes, seed=100 + a)
        parts = data_mod.dirichlet_partition(y, w, alpha=1.0, seed=200 + a)
        parts = [p if len(p) else np.arange(3) for p in parts]
        ws = [int(n) for n in rng.choice(nodes, size=w, replace=False)]
        apps.append(
            rounds.make_app(
                sys_, f"async{tag}-{m}-{a}", workers=ws,
                data_by_worker={n: (x[parts[i]], y[parts[i]]) for i, n in enumerate(ws)},
                dim=dim, num_classes=classes, local_steps=3, lr=0.2, seed=a,
            )
        )
    return apps


def _time_to_target(ts, losses, target):
    for t, l in zip(ts, losses):
        if l <= target:
            return float(t)
    return float("inf")


def _run_async_variant(variant, m_apps, *, workers, rounds_n, seed, target,
                       base_ms, spread, model_bytes, n_nodes, zones):
    """One async run (fresh system, shared seeds -> identical topology,
    shards, compute draws and churn schedule across variants)."""
    from repro.core.sim import ChurnModel
    from repro.fl import async_engine
    from repro.fl.selection import UtilitySelector

    per_worker = async_engine.worker_compute_fn(base_ms, spread, seed=seed)
    sys_a, nodes_a, rng_a = build_system(n_nodes=n_nodes, zones=zones, seed=seed)
    apps_a = _make_apps(sys_a, nodes_a, rng_a, m_apps, workers, tag="a")
    churn = ChurnModel(
        period_ms=6.0 * base_ms, downtime_ms=12.0 * base_ms,
        group_size=max(1, round(0.1 * workers)), seed=seed,
    )
    kwargs = {}
    if variant in ("adaptive", "adaptive+utility"):
        kwargs["adaptive"] = True
        kwargs["adaptive_kwargs"] = {"target_staleness": 1.0, "percentile": 75.0}
    if variant == "adaptive+utility":
        kwargs["selector"] = UtilitySelector(
            deadline_ms=6.0 * base_ms, epsilon=0.1, admit_quantile=0.35, seed=seed,
        )
    res = async_engine.run_async(
        sys_a, apps_a, applies=2 * rounds_n, buffer_k=max(2, workers // 2),
        staleness_alpha=0.5, model_bytes=model_bytes, compute_ms=per_worker,
        churn=churn, **kwargs,
    )
    tts = []
    for app in apps_a:
        h = [r for r in res["history"] if r["app_id"] == app.handle.app_id]
        tts.append(_time_to_target([r["t_ms"] for r in h], [r["loss"] for r in h], target))
    failed_once = {n for c in res["churn"] if c.kind == "fail" for n in c.nodes}
    stal = [e.mean_staleness for e in res["events"]]
    ks = [e.k for e in res["events"]]
    return {
        "tt_ms": float(np.mean(tts)),
        "churn_fraction": len(failed_once) / float(m_apps * workers),
        "churn_events": len(res["churn"]),
        "mean_staleness": float(np.mean(stal)) if stal else 0.0,
        "mean_k": float(np.mean(ks)) if ks else 0.0,
    }


def compare(m_apps: int, *, workers=8, rounds_n=5, seed=0, target=0.5,
            base_ms=40.0, spread=6.0, model_bytes=2e5, n_nodes=600, zones=4) -> dict:
    """One full comparison at M concurrent apps; returns per-variant metrics.
    The topology constants (``n_nodes``, ``zones``) are shared between the
    sync baseline and every async variant — that's what makes the
    comparison isolate the control policy."""
    from repro.core.sim import SyncRoundScheduler, per_app_round_ms
    from repro.fl import async_engine, rounds

    per_worker = async_engine.worker_compute_fn(base_ms, spread, seed=seed)

    # (a) synchronous baseline: barrier waits for the slowest worker; no churn
    sys_s, nodes_s, rng_s = build_system(n_nodes=n_nodes, zones=zones, seed=seed)
    apps_s = _make_apps(sys_s, nodes_s, rng_s, m_apps, workers, tag="s")
    sched = SyncRoundScheduler(
        sys_s, [a.handle for a in apps_s], model_bytes=model_bytes,
        compute_ms=async_engine.sync_barrier_compute_fn(per_worker),
    )
    hist = sched.run(rounds=rounds_n)
    sync_t = {aid: np.cumsum(v) for aid, v in per_app_round_ms(hist).items()}
    sync_tt = []
    for app in apps_s:
        losses = [rounds.run_round(sys_s, app)["loss"] for _ in range(rounds_n)]
        sync_tt.append(_time_to_target(sync_t[app.handle.app_id], losses, target))

    # (b) async variants: same seeds/topology/churn, different control policy
    cfg = dict(workers=workers, rounds_n=rounds_n, seed=seed, target=target,
               base_ms=base_ms, spread=spread, model_bytes=model_bytes,
               n_nodes=n_nodes, zones=zones)
    variants = {v: _run_async_variant(v, m_apps, **cfg)
                for v in ("fixed", "adaptive", "adaptive+utility")}
    fixed, adap, util = variants["fixed"], variants["adaptive"], variants["adaptive+utility"]
    return {
        "m": m_apps,
        "workers": workers,
        "target_loss": target,
        "sync_tt_ms": float(np.mean(sync_tt)),
        "fixed_tt_ms": fixed["tt_ms"],
        "adaptive_tt_ms": adap["tt_ms"],
        "adaptive_utility_tt_ms": util["tt_ms"],
        "speedup_vs_sync": float(np.mean(sync_tt)) / max(util["tt_ms"], 1e-9),
        "utility_vs_fixed": fixed["tt_ms"] / max(util["tt_ms"], 1e-9),
        "churn_fraction": fixed["churn_fraction"],
        "variants": variants,
    }


def run() -> list[str]:
    out = []
    for m in (1, 4, 16):
        r = compare(m)
        out.append(
            row(
                f"async_vs_sync_m{m}",
                0.0,
                f"sync_tt_ms={r['sync_tt_ms']:.0f};fixed_tt_ms={r['fixed_tt_ms']:.0f};"
                f"adaptive_tt_ms={r['adaptive_tt_ms']:.0f};"
                f"adaptive_utility_tt_ms={r['adaptive_utility_tt_ms']:.0f};"
                f"utility_vs_fixed={r['utility_vs_fixed']:.2f}x;"
                f"churn_frac={r['churn_fraction']:.2f}",
            )
        )
    return out


def _json_safe(obj):
    """inf (a variant that never hit the target) -> null: json.dump would
    otherwise emit bare ``Infinity``, which is not valid JSON."""
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, float) and not np.isfinite(obj):
        return None
    return obj


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--smoke", action="store_true", help="small config; write BENCH_async.json")
    ap.add_argument("--out", default="BENCH_async.json")
    args = ap.parse_args()
    ms = (1, 4) if args.smoke else (1, 4, 16)
    rounds_n = 3 if args.smoke else 5
    results = [compare(m, rounds_n=rounds_n) for m in ms]
    fairness = None
    if args.smoke:
        from benchmarks import bench_fairness

        fairness = {
            "matrix": [bench_fairness.fairness_compare(16)],
            "time_to_loss_guard": bench_fairness.time_to_loss_guard(),
        }
    payload = {
        "bench": "async_time_to_target_fixed_vs_adaptive_vs_utility",
        "smoke": bool(args.smoke),
        "results": _json_safe(results),
        "fairness": _json_safe(fairness),
    }
    out_path = os.path.abspath(args.out)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, allow_nan=False)
    for r in results:
        print(
            f"M={r['m']}: sync={r['sync_tt_ms']:.0f}ms fixed={r['fixed_tt_ms']:.0f}ms "
            f"adaptive={r['adaptive_tt_ms']:.0f}ms "
            f"adaptive+utility={r['adaptive_utility_tt_ms']:.0f}ms "
            f"(utility vs fixed {r['utility_vs_fixed']:.2f}x, churn {r['churn_fraction']:.0%})"
        )
    ok_sync = all(r["sync_tt_ms"] >= r["adaptive_utility_tt_ms"] for r in results)
    ok_fixed = all(
        np.isfinite(r["adaptive_utility_tt_ms"])
        and r["adaptive_utility_tt_ms"] <= r["fixed_tt_ms"]
        for r in results
        if r["m"] >= 4
    )
    # every variant of every M must have seen >= 10% churn, not just fixed
    ok_churn = all(
        v["churn_fraction"] >= 0.10 for r in results for v in r["variants"].values()
    )
    print(f"wrote {out_path}")
    print(
        f"adaptive+utility <= fixed at M>=4: {ok_fixed}; beats sync: {ok_sync}; "
        f"churn >= 10% in every variant: {ok_churn}"
    )
    fairness_fails = []
    if fairness is not None:
        from benchmarks import bench_fairness

        r = fairness["matrix"][0]
        g = fairness["time_to_loss_guard"]
        print(
            f"fairness M=16: jain {r['jain_fair']:.3f}; "
            f"time-to-loss spread {g['spread_fair']:.2f}x"
        )
        fairness_fails = bench_fairness.gate(fairness["matrix"], g)
        for msg in fairness_fails:
            print(f"GATE FAIL: {msg}")
    if not (ok_fixed and ok_sync and ok_churn) or fairness_fails:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
