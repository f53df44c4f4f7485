"""Live placement vs static trees: time-to-target-loss under churn.

M apps with small (300 KB) models, heterogeneous compute, and churn
whose period is scale-matched to the cycle length (seconds, not the
milliseconds-scale churn of ``bench_async`` — a placement layer cannot
help if no cycle ever survives between failures).  Each configuration
runs twice on identical seeds: once with static trees
(``placement=None``) and once with the default ``PlacementEngine``
closing the loop planner → forest re-graft → event core → selector.

Gates (``gate_placement``):

- placed mean simulated time-to-target-loss <= 0.95x static at every M;
- Jain's index over per-app completion rates is no worse than static;
- >= 10% of workers fail at least once in both runs (the churn floor
  the comparison is claimed under);
- trace identity: an explicit ``placement=None`` run is byte-identical
  (apply/churn-trace digest) to a run that never mentions placement —
  the closed loop is pay-for-what-you-use.

``python -m benchmarks.bench_placement --smoke`` runs M=16 and writes
``BENCH_placement.json`` (a CI artifact); the full run adds M=64.
Everything is seeded and deterministic.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import build_system, row

SMOKE_MS = (16,)   # --smoke stays bounded at M <= 16
FULL_MS = (16, 64)

# Fixture: commit uplink matters (300 KB over 20-100 Mbps shared hops
# ~ tens to hundreds of ms) but cycles complete between churn events
# (period 1.5 s >> cycle ~ 0.2-0.5 s).  group_size keeps ~5% of all
# workers down at any instant, which over a run fails well over 10% of
# workers at least once.  The churn window is bounded
# (max_fail_events): churn volume must be a property of the scenario,
# not of the run length — an unbounded model feeds back (a slower run
# absorbs proportionally more failures, which makes it slower still),
# which contaminates any static-vs-placed comparison and can stall a
# straggler app indefinitely at M=64.
WORKERS = 5
APPLIES = 8
MODEL_BYTES = 3e5
BASE_MS = 4.0
TARGET_LOSS = 0.35
CHURN_PERIOD_MS = 1500.0
CHURN_DOWNTIME_MS = 3000.0
CHURN_MAX_FAILS = 32


def _make_apps(sys_, nodes, rng, m):
    from repro import data as data_mod
    from repro.fl import rounds

    apps = []
    for a in range(m):
        x, y = data_mod.synthetic_classification(WORKERS * 24, 16, 4, seed=100 + a)
        parts = data_mod.dirichlet_partition(y, WORKERS, alpha=1.0, seed=200 + a)
        ws = [int(n) for n in rng.choice(nodes, size=WORKERS, replace=False)]
        apps.append(
            rounds.make_app(
                sys_,
                f"plc-{m}-{a}",
                workers=ws,
                data_by_worker={n: (x[parts[i]], y[parts[i]]) for i, n in enumerate(ws)},
                dim=16,
                num_classes=4,
                local_steps=3,
                lr=0.2,
                seed=a,
            )
        )
    return apps


def _time_to_loss(history, app_id, target=TARGET_LOSS):
    for r in history:
        if r["app_id"] == app_id and r["loss"] <= target:
            return float(r["t_ms"])
    return float("inf")


def _run_once(m, seed, placement, *, pass_kwarg=True):
    from repro.core.sim import ChurnModel
    from repro.fl import async_engine

    sys_, nodes, rng = build_system(n_nodes=max(96, 5 * m), zones=8, seed=seed)
    apps = _make_apps(sys_, nodes, rng, m)
    churn = ChurnModel(
        period_ms=CHURN_PERIOD_MS,
        downtime_ms=CHURN_DOWNTIME_MS,
        group_size=max(1, round(0.05 * m * WORKERS)),
        seed=seed + 3,
        max_fail_events=CHURN_MAX_FAILS,
    )
    kw = {"placement": placement} if pass_kwarg else {}
    res = async_engine.run_async(
        sys_,
        apps,
        applies=APPLIES,
        buffer_k=4,
        staleness_alpha=0.5,
        model_bytes=MODEL_BYTES,
        compute_ms=async_engine.worker_compute_fn(20.0, 3.0, seed),
        base_ms=BASE_MS,
        churn=churn,
        max_events=8_000_000,
        **kw,
    )
    return res, [a.handle.app_id for a in apps]


def _churn_fraction(sched, m):
    failed_once = set()
    for c in sched.churn_log:
        if c.kind == "fail":
            failed_once.update(c.nodes)
    allw = set().union(*[set(sched._orig_workers[ai]) for ai in range(m)])
    return len(failed_once & allw) / max(len(allw), 1)


def _trace_digest(sched) -> str:
    h = hashlib.sha256()
    for ev in sched.history:  # ApplyEvent dataclasses: repr is total
        h.update(repr(ev).encode())
    for c in sched.churn_log:
        h.update(repr(c).encode())
    for f in sched.fairness_log:
        h.update(repr(f).encode())
    return h.hexdigest()


def placement_compare(m: int, *, seed: int = 0) -> dict:
    """Static vs placed run on identical seeds; returns gate inputs."""
    from repro.core.pathplan import PlacementEngine
    from repro.kernels.ops import jain_fairness

    res_s, ids = _run_once(m, seed, None)
    res_p, _ = _run_once(m, seed, PlacementEngine(cooldown_ms=5000.0))
    ss, sp = res_s["scheduler"], res_p["scheduler"]

    tts_s = [_time_to_loss(res_s["history"], i) for i in ids]
    tts_p = [_time_to_loss(res_p["history"], i) for i in ids]
    rate_s = [1.0 / max(t, 1e-9) for t in tts_s]
    rate_p = [1.0 / max(t, 1e-9) for t in tts_p]
    ratios = [p / s for p, s in zip(tts_p, tts_s)]
    return {
        "m": m,
        "tt_static_ms": tts_s,
        "tt_placed_ms": tts_p,
        "mean_tt_ratio": float(np.mean(tts_p) / np.mean(tts_s)),
        "max_tt_ratio": float(max(ratios)),
        "jain_static": float(jain_fairness(rate_s)),
        "jain_placed": float(jain_fairness(rate_p)),
        "churn_frac_static": _churn_fraction(ss, m),
        "churn_frac_placed": _churn_fraction(sp, m),
        "replans": len(sp.replan_log),
        "moves": int(sum(len(r.moves) for r in sp.replan_log)),
        "replan_cost_ms": float(sum(r.cost_ms for r in sp.replan_log)),
        "control_bytes": float(sp.control_bytes),
    }


def trace_identity(m: int = 16, *, seed: int = 0) -> dict:
    """`placement=None` must not perturb a single event vs the legacy path."""
    res_a, _ = _run_once(m, seed, None, pass_kwarg=True)
    res_b, _ = _run_once(m, seed, None, pass_kwarg=False)
    da = _trace_digest(res_a["scheduler"])
    db = _trace_digest(res_b["scheduler"])
    return {"m": m, "digest_none": da, "digest_legacy": db, "identical": da == db}


def gate_placement(results: list[dict], ident: dict) -> list[str]:
    fails = []
    if not ident["identical"]:
        fails.append(
            f"placement=None trace digest {ident['digest_none'][:12]} != "
            f"legacy {ident['digest_legacy'][:12]} at M={ident['m']}"
        )
    for r in results:
        m = r["m"]
        if r["mean_tt_ratio"] > 0.95:
            fails.append(
                f"M={m}: placed mean time-to-loss {r['mean_tt_ratio']:.3f}x > 0.95x static"
            )
        if r["jain_placed"] < r["jain_static"] - 1e-3:
            fails.append(
                f"M={m}: Jain worsened {r['jain_static']:.3f} -> {r['jain_placed']:.3f}"
            )
        for key in ("churn_frac_static", "churn_frac_placed"):
            if r[key] < 0.10:
                fails.append(f"M={m}: {key}={r[key]:.2f} < 0.10 churn floor")
    return fails


def run() -> list[str]:
    out = []
    for m in SMOKE_MS:
        r = placement_compare(m)
        out.append(
            row(
                f"placement_m{m}",
                0.0,
                f"mean_tt_ratio={r['mean_tt_ratio']:.3f};"
                f"jain={r['jain_static']:.3f}->{r['jain_placed']:.3f};"
                f"moves={r['moves']};replan_cost_ms={r['replan_cost_ms']:.0f}",
            )
        )
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--smoke", action="store_true",
                    help="M=16 only; write BENCH_placement.json")
    ap.add_argument("--out", default="BENCH_placement.json")
    args = ap.parse_args(argv)

    ident = trace_identity(16)
    print(f"trace identity (placement=None vs legacy, M=16): {ident['identical']}")
    results = [placement_compare(m) for m in (SMOKE_MS if args.smoke else FULL_MS)]
    for r in results:
        print(
            f"M={r['m']}: time-to-loss placed/static mean {r['mean_tt_ratio']:.3f}x "
            f"(worst {r['max_tt_ratio']:.2f}x)  "
            f"jain {r['jain_static']:.3f}->{r['jain_placed']:.3f}  "
            f"churn {r['churn_frac_static']:.2f}/{r['churn_frac_placed']:.2f}  "
            f"replans={r['replans']} moves={r['moves']} "
            f"cost={r['replan_cost_ms']:.0f}ms"
        )

    from benchmarks.bench_async import _json_safe

    payload = _json_safe({
        "bench": "live_placement",
        "smoke": bool(args.smoke),
        "trace_identity": ident,
        "results": results,
    })
    out_path = os.path.abspath(args.out)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, allow_nan=False)
    print(f"wrote {out_path}")

    fails = gate_placement(results, ident)
    for msg in fails:
        print(f"GATE FAIL: {msg}")
    if fails:
        raise SystemExit(1)
    print("placement gates passed: placed mean time-to-target <= 0.95x static, "
          "Jain no worse, >=10% churn, placement=None trace identical")


if __name__ == "__main__":
    main()
