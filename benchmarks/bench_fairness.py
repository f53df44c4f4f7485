"""Multi-app uplink fairness under weighted-fair transfer pricing.

Every transfer hop is a fluid flow re-priced whenever a flow joins or
leaves its uplink, so no app keeps a stale solo (or stale congested)
rate; at M >= 16 apps sharing one edge network a start-time price
compounds into uplink starvation (the Table-III scaling claim bends).
This bench measures per-app progress on an M ∈ {4, 16, 64} matrix with
**one hot app** (near-zero compute, so its workers hammer the shared
relays continuously) against M-1 compute-bound apps:

- **fairness matrix** (timing-only): every app moves the same transfer
  workload (a fixed number of buffered applies); the per-app *uplink
  progress rate* is its solo completion time on the same topology
  divided by its contended completion time (1.0 = as fast as running
  alone — solo-normalized throughput, the standard way to compare apps
  with different demands, and free of horizon-cut truncation bias).
  Jain's index over those rates is gated **>= 0.8** (weighted-fair
  pricing with relay admission).
- **time-to-loss guard** (trained, M = 16): the same hot/cold mix with
  real training; per-app simulated time until the mean local loss
  reaches the target.  Gated: **every app reaches the target**; the
  max/min spread across apps is reported.

``python -m benchmarks.bench_fairness --smoke`` runs M ∈ {4, 16} plus
the trained guard and writes ``BENCH_fairness.json`` (a CI artifact);
the full run adds the M = 64 column.  Everything is seeded and
deterministic.
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

# one column per M: the topology scales with the app count so the matrix
# stays in the contended-but-feasible regime (oversubscribed enough to
# starve under the seed pricing, not so overloaded that nothing moves)
CONFIGS = {
    4: dict(n_nodes=120, workers=8, model_bytes=1.5e6, applies=4, buffer_k=4),
    16: dict(n_nodes=120, workers=8, model_bytes=1.5e6, applies=4, buffer_k=4),
    64: dict(n_nodes=320, workers=4, model_bytes=8e5, applies=4, buffer_k=2),
}
HOT_MS, COLD_MS = 2.0, 40.0


def _build_handles(m, workers, n_nodes, seed=0):
    """Timing-only fixture: M dataflow trees over one shared overlay."""
    from repro.core.api import TotoroSystem

    sys_ = TotoroSystem(zone_bits=2, suffix_bits=22, seed=seed)
    rng = np.random.default_rng(seed)
    nodes = [
        sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2),
                  bandwidth=float(rng.uniform(20, 100)))
        for i in range(n_nodes)
    ]
    handles = []
    for a in range(m):
        h = sys_.CreateTree(f"fairness-{m}-{a}")
        for w in rng.choice(nodes, size=workers, replace=False):
            sys_.Subscribe(h.app_id, int(w))
        handles.append(h)
    return sys_, handles


def _admission():
    from repro.core.sim import RelayAdmission

    return RelayAdmission(threshold=0.6, alpha=0.5, max_defer_ms=150.0)


def fairness_compare(m: int, *, seed: int = 0) -> dict:
    """One matrix column: weighted-fair pricing with relay admission.
    Every app completes the same applies target in every run (no
    horizon truncation); the per-app progress rate is solo completion
    time / contended completion time, so 1.0 means the app ran as fast
    as it would alone."""
    from repro.core.sim import AsyncBufferScheduler
    from repro.kernels.ops import jain_fairness

    cfg = CONFIGS[m]
    sys_, handles = _build_handles(m, cfg["workers"], cfg["n_nodes"], seed=seed)
    hot_id = handles[0].app_id

    def compute(handle, worker, cycle):
        return HOT_MS if handle.app_id == hot_id else COLD_MS

    def run(relay=None, subset=None):
        hs = handles if subset is None else [handles[i] for i in subset]
        sched = AsyncBufferScheduler(
            sys_, hs, model_bytes=cfg["model_bytes"], compute_ms=compute,
            buffer_k=cfg["buffer_k"], relay_admission=relay,
        )
        sched.run(cfg["applies"], max_events=8_000_000)
        return sched.transport_stats()

    # solo baseline: each app alone on the same topology — its own
    # workers still share intra-app relays
    solo = [run(subset=[a])["done_ms"][0] for a in range(m)]
    fair = run(_admission())
    r_fair = [s / max(d, 1e-9) for s, d in zip(solo, fair["done_ms"])]
    return {
        "m": m,
        "jain_fair": jain_fairness(r_fair),
        "hot_ratio_fair": r_fair[0],
        "min_ratio_fair": min(r_fair),
        "deferred_commits": fair["deferred_commits"],
        "jain_bytes_fair": jain_fairness(fair["uplink_bytes"]),
        "ratios_fair": r_fair,
    }


def time_to_loss_guard(*, m: int = 16, seed: int = 0, target: float = 0.35) -> dict:
    """Trained run at M apps with one hot app: every app must reach the
    target loss; the cross-app spread of time-to-target is reported."""
    from repro import data as data_mod
    from repro.fl import async_engine, rounds

    workers, applies = 8, 14

    def make_apps(sys_, nodes, rng):
        apps = []
        for a in range(m):
            x, y = data_mod.synthetic_classification(workers * 24, 16, 4, seed=100 + a)
            parts = data_mod.dirichlet_partition(y, workers, alpha=1.0, seed=200 + a)
            ws = [int(n) for n in rng.choice(nodes, size=workers, replace=False)]
            apps.append(
                rounds.make_app(
                    sys_, f"ttl-{m}-{a}", workers=ws,
                    data_by_worker={n: (x[parts[i]], y[parts[i]]) for i, n in enumerate(ws)},
                    dim=16, num_classes=4, local_steps=3, lr=0.2, seed=a,
                )
            )
        return apps

    def tt(history, app_id):
        for r in history:
            if r["app_id"] == app_id and r["loss"] <= target:
                return r["t_ms"]
        return float("inf")

    sys_, nodes, rng = build_system(n_nodes=300, zones=4, seed=seed)
    apps = make_apps(sys_, nodes, rng)
    hot_id = apps[0].handle.app_id

    def compute(handle, worker, cycle):
        if handle.app_id == hot_id:
            return 5.0
        slow = np.random.default_rng([7, handle.app_id, worker])
        return COLD_MS * (1.0 + 3.0 * float(slow.random()))

    res = async_engine.run_async(
        sys_, apps, applies=applies, buffer_k=4, staleness_alpha=0.5,
        model_bytes=4e5, compute_ms=compute, relay_admission=_admission(),
    )
    tt_fair = [tt(res["history"], a.handle.app_id) for a in apps]
    finite = [t for t in tt_fair if np.isfinite(t)]
    return {
        "m": m,
        "target_loss": target,
        "tt_fair_ms": tt_fair,
        "spread_fair": max(finite) / max(min(finite), 1e-9) if finite else float("inf"),
        "all_finite": len(finite) == len(tt_fair),
    }


def compression_compare(
    m: int = 64, *, seed: int = 0, target: float = 0.35
) -> dict:
    """The tight-uplink compression axis (docs/performance.md "compressed
    transport"): M apps with near-zero compute and a big model, so the
    commit uplink is the bottleneck; qsgd-int8 vs uncompressed on the
    identical topology/schedule.  Gated (``gate_compression``): the mean
    simulated time-to-target-loss must clearly improve under compression
    (the ~4x smaller commit flows must actually buy wall-clock; no
    single app may regress > 25% — a starvation guard, sized to tolerate
    one-apply quantization shifts in the crossing time), and the mean
    final loss may not drift more than 1e-2 from the uncompressed run
    (int8 rounding must stay statistically free)."""
    from repro import data as data_mod
    from repro.fl import async_engine, rounds

    workers, applies, model_bytes = 4, 12, 2e6
    n_nodes = max(80, 5 * m)

    def make_apps(sys_, nodes, rng):
        apps = []
        for a in range(m):
            x, y = data_mod.synthetic_classification(workers * 24, 16, 4, seed=100 + a)
            parts = data_mod.dirichlet_partition(y, workers, alpha=1.0, seed=200 + a)
            ws = [int(n) for n in rng.choice(nodes, size=workers, replace=False)]
            apps.append(
                rounds.make_app(
                    sys_, f"comp-{m}-{a}", workers=ws,
                    data_by_worker={n: (x[parts[i]], y[parts[i]]) for i, n in enumerate(ws)},
                    dim=16, num_classes=4, local_steps=3, lr=0.2, seed=a,
                )
            )
        return apps

    def tt(history, app_id):
        for r in history:
            if r["app_id"] == app_id and r["loss"] <= target:
                return r["t_ms"]
        return float("inf")

    def run(compression):
        sys_, nodes, rng = build_system(n_nodes=n_nodes, zones=4, seed=seed)
        apps = make_apps(sys_, nodes, rng)
        res = async_engine.run_async(
            sys_, apps, applies=applies, buffer_k=4, staleness_alpha=0.5,
            model_bytes=model_bytes, compute_ms=5.0,
            compression=compression, max_events=8_000_000,
        )
        final = {}
        for r in res["history"]:  # last apply per app wins
            final[r["app_id"]] = r["loss"]
        ids = [a.handle.app_id for a in apps]
        up = res["scheduler"].transport_stats()["uplink_bytes"]
        return [tt(res["history"], i) for i in ids], [final[i] for i in ids], up

    tt_none, loss_none, up_none = run(None)
    tt_qsgd, loss_qsgd, up_qsgd = run("qsgd-int8")
    ratio = [q / max(n, 1e-9) for q, n in zip(tt_qsgd, tt_none)]
    return {
        "m": m,
        "target_loss": target,
        "model_bytes": model_bytes,
        "tt_none_ms": tt_none,
        "tt_qsgd_ms": tt_qsgd,
        "tt_ratio": ratio,
        "mean_tt_ratio": float(np.mean(ratio)),
        "max_tt_ratio": max(ratio),
        "loss_none": loss_none,
        "loss_qsgd": loss_qsgd,
        "loss_gap": abs(float(np.mean(loss_qsgd)) - float(np.mean(loss_none))),
        "bytes_ratio": float(sum(up_qsgd) / max(sum(up_none), 1e-9)),
        "all_finite": bool(all(np.isfinite(t) for t in tt_none + tt_qsgd)),
    }


def downlink_compare(
    m: int = 16, *, seed: int = 0, target: float = 0.35
) -> dict:
    """The tight-downlink axis (docs/performance.md "compressed
    downlink"): the same commit-bound fixture as ``compression_compare``
    — near-zero compute, 2 MB model, K = W so workers re-download every
    version — where the full-f32 broadcast leg is ~80% of the wire.
    Three runs on the identical topology/schedule: uncompressed,
    uplink-only qsgd-int8 (the PR-8 baseline), and uplink qsgd +
    delta-qsgd downlink (3-bit packed version deltas, ``chain_cap=3``).
    Gated (``gate_downlink``) against the uplink-only baseline: total
    wire bytes (up + down) < 0.35x, mean time-to-target <= 0.90x, Jain
    over per-app progress no worse, and a 25% per-app starvation
    guard."""
    from repro import data as data_mod
    from repro.fl import async_engine, rounds
    from repro.fl.compression import CompressionPolicy
    from repro.kernels.ops import jain_fairness

    workers, applies, model_bytes = 4, 12, 2e6
    n_nodes = max(80, 5 * m)

    def make_apps(sys_, nodes, rng):
        apps = []
        for a in range(m):
            x, y = data_mod.synthetic_classification(workers * 24, 16, 4, seed=100 + a)
            parts = data_mod.dirichlet_partition(y, workers, alpha=1.0, seed=200 + a)
            ws = [int(n) for n in rng.choice(nodes, size=workers, replace=False)]
            apps.append(
                rounds.make_app(
                    sys_, f"down-{m}-{a}", workers=ws,
                    data_by_worker={n: (x[parts[i]], y[parts[i]]) for i, n in enumerate(ws)},
                    dim=16, num_classes=4, local_steps=3, lr=0.2, seed=a,
                )
            )
        return apps

    def tt(history, app_id):
        for r in history:
            if r["app_id"] == app_id and r["loss"] <= target:
                return r["t_ms"]
        return float("inf")

    def run(compression):
        sys_, nodes, rng = build_system(n_nodes=n_nodes, zones=4, seed=seed)
        apps = make_apps(sys_, nodes, rng)
        res = async_engine.run_async(
            sys_, apps, applies=applies, buffer_k=4, staleness_alpha=0.5,
            model_bytes=model_bytes, compute_ms=5.0,
            compression=compression, max_events=8_000_000,
        )
        ids = [a.handle.app_id for a in apps]
        st = res["scheduler"].transport_stats()
        return {
            "tt": [tt(res["history"], i) for i in ids],
            "up": sum(st["uplink_bytes"]),
            "down": sum(st["downlink_bytes"]),
        }

    up_only = run(CompressionPolicy(kind="qsgd-int8"))
    up_down = run(CompressionPolicy(
        kind="qsgd-int8", downlink="delta-qsgd", downlink_levels=3, chain_cap=3
    ))
    none = run(None)

    def jain_progress(r):
        return jain_fairness([1.0 / max(t, 1e-9) for t in r["tt"]])

    ratio = [d / max(u, 1e-9) for d, u in zip(up_down["tt"], up_only["tt"])]
    total_up_only = up_only["up"] + up_only["down"]
    total_up_down = up_down["up"] + up_down["down"]
    return {
        "m": m,
        "target_loss": target,
        "model_bytes": model_bytes,
        "tt_none_ms": none["tt"],
        "tt_up_only_ms": up_only["tt"],
        "tt_up_down_ms": up_down["tt"],
        "tt_ratio": ratio,
        "mean_tt_ratio": float(np.mean(ratio)),
        "max_tt_ratio": max(ratio),
        "bytes_up_only": total_up_only,
        "bytes_up_down": total_up_down,
        "bytes_none": none["up"] + none["down"],
        "bytes_total_ratio": float(total_up_down / max(total_up_only, 1e-9)),
        "downlink_bytes_ratio": float(up_down["down"] / max(up_only["down"], 1e-9)),
        "jain_up_only": jain_progress(up_only),
        "jain_up_down": jain_progress(up_down),
        "all_finite": bool(
            all(np.isfinite(t) for t in up_only["tt"] + up_down["tt"])
        ),
    }


def gate_downlink(rows: list[dict]) -> list[str]:
    """Compressed-downlink acceptance gates; human-readable failures."""
    fails = []
    for r in rows:
        if not r["all_finite"]:
            fails.append(f"downlink M={r['m']}: an app never hit the target loss")
        if r["bytes_total_ratio"] >= 0.35:
            fails.append(
                f"downlink M={r['m']}: total wire bytes "
                f"{r['bytes_total_ratio']:.3f}x >= 0.35x uplink-only baseline"
            )
        if r["mean_tt_ratio"] > 0.90:
            fails.append(
                f"downlink M={r['m']}: mean time-to-target "
                f"{r['mean_tt_ratio']:.2f} > 0.90x (compressed broadcasts "
                f"must buy wall-clock)"
            )
        # starvation guard (same rationale as gate_compression: the apply
        # quantization of time-to-target tolerates one-apply shifts)
        if r["max_tt_ratio"] > 1.25:
            fails.append(
                f"downlink M={r['m']}: an app regressed "
                f"{(r['max_tt_ratio'] - 1) * 100:.1f}% (> 25%)"
            )
        # fp slack only — the downlink must not redistribute progress
        if r["jain_up_down"] < r["jain_up_only"] - 0.02:
            fails.append(
                f"downlink M={r['m']}: jain worsened "
                f"({r['jain_up_only']:.3f} -> {r['jain_up_down']:.3f})"
            )
    return fails


def gate_compression(rows: list[dict]) -> list[str]:
    """Compressed-transport acceptance gates; human-readable failures."""
    fails = []
    for r in rows:
        if not r["all_finite"]:
            fails.append(f"compression M={r['m']}: an app never hit the target loss")
        if r["mean_tt_ratio"] >= 0.95:
            fails.append(
                f"compression M={r['m']}: mean time-to-target did not clearly "
                f"improve (qsgd/none {r['mean_tt_ratio']:.2f} >= 0.95)"
            )
        # starvation guard, not a per-app improvement gate: time-to-target
        # is quantized by apply events, so a rescheduled app can cross one
        # apply later (~10% here) without anything being wrong
        if r["max_tt_ratio"] > 1.25:
            fails.append(
                f"compression M={r['m']}: an app regressed "
                f"{(r['max_tt_ratio'] - 1) * 100:.1f}% (> 25%) under compression"
            )
        if r["loss_gap"] > 1e-2:
            fails.append(
                f"compression M={r['m']}: loss gap {r['loss_gap']:.4f} > 1e-2"
            )
        if r["bytes_ratio"] > 0.3:
            fails.append(
                f"compression M={r['m']}: uplink bytes ratio "
                f"{r['bytes_ratio']:.3f} > 0.3 (int8+scales should be ~0.26x)"
            )
    return fails


def gate(results: list[dict], guard: dict | None) -> list[str]:
    """The fairness acceptance gates; returns human-readable failures."""
    fails = []
    for r in results:
        if r["jain_fair"] < 0.8:
            fails.append(f"M={r['m']}: jain_fair {r['jain_fair']:.3f} < 0.8")
    if guard is not None and not guard["all_finite"]:
        fails.append("time-to-loss guard: some app never reached the target")
    return fails


def run() -> list[str]:
    out = []
    for m in sorted(CONFIGS):
        r = fairness_compare(m)
        out.append(
            row(
                f"fairness_m{m}",
                0.0,
                f"jain_fair={r['jain_fair']:.3f};"
                f"hot_ratio={r['hot_ratio_fair']:.2f};"
                f"deferred={r['deferred_commits']}",
            )
        )
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--smoke", action="store_true",
                    help="M in {4,16} + trained guard; write BENCH_fairness.json")
    ap.add_argument("--out", default="BENCH_fairness.json")
    args = ap.parse_args(argv)

    ms = (4, 16) if args.smoke else tuple(sorted(CONFIGS))
    results = [fairness_compare(m) for m in ms]
    for r in results:
        print(
            f"M={r['m']}: jain={r['jain_fair']:.3f}  "
            f"hot app ratio {r['hot_ratio_fair']:.2f}  "
            f"min ratio {r['min_ratio_fair']:.2f}  "
            f"deferred={r['deferred_commits']}"
        )
    guard = time_to_loss_guard()
    print(
        f"time-to-loss (M={guard['m']}, target {guard['target_loss']}): "
        f"every app reached it: {guard['all_finite']}, spread {guard['spread_fair']:.2f}"
    )

    from benchmarks.bench_async import _json_safe

    payload = _json_safe({
        "bench": "multi_app_uplink_fairness",
        "smoke": bool(args.smoke),
        "results": results,
        "time_to_loss_guard": guard,
    })
    out_path = os.path.abspath(args.out)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, allow_nan=False)
    print(f"wrote {out_path}")

    fails = gate(results, guard)
    for msg in fails:
        print(f"GATE FAIL: {msg}")
    if fails:
        raise SystemExit(1)
    print("fairness gates passed: jain >= 0.8, every app reached the target loss")


if __name__ == "__main__":
    main()
