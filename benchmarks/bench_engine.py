"""Event-driven multi-app simulator benchmark.

Table III: per-app round completion time for M in {1, 4, 16}
concurrent apps on one overlay, priced by the discrete-event simulator
(``core/sim.py``, shared-link contention where trees overlap) vs the
centralized single-coordinator queue (``fl/rounds.CentralizedBaseline``).
"""
from __future__ import annotations

import types

import numpy as np

from .common import build_system, row


def run() -> list[str]:
    from repro.core.sim import MultiAppSimulator, per_app_round_ms
    from repro.fl import rounds

    out = []
    sys_, nodes, rng = build_system(n_nodes=1200, zones=4, seed=11)
    dim, classes = 32, 8

    # Table-III curve: M concurrent apps, shared links vs central queue
    model_bytes = 4.0 * (dim * 32 + 32 + 32 * 32 + 32 + 32 * classes + classes)
    compute_ms = 40.0
    base = rounds.CentralizedBaseline()
    for M in (1, 4, 16):
        handles = []
        for a in range(M):
            h = sys_.CreateTree(f"tab3-{M}-{a}")
            for w in rng.choice(nodes, size=32, replace=False):
                sys_.Subscribe(h.app_id, int(w))
            handles.append(h)
        sim = MultiAppSimulator(sys_, handles, model_bytes=model_bytes, compute_ms=compute_ms)
        hist = sim.run(rounds=3)
        per_app = per_app_round_ms(hist)
        totoro_ms = float(np.mean([np.mean(v) for v in per_app.values()]))
        shims = [
            types.SimpleNamespace(data={w: None for w in h.tree.members})
            for h in handles
        ]
        central = base.round_time_ms(shims, compute_ms, model_bytes)
        central_ms = float(np.mean(central))  # mean per-app completion in the queue
        out.append(
            row(
                f"tab3_sim_m{M}",
                0.0,
                f"totoro_round_ms={totoro_ms:.1f};central_round_ms={central_ms:.1f};"
                f"speedup={central_ms/max(totoro_ms,1e-9):.1f}x",
            )
        )
        for h in handles:
            sys_.apps.pop(h.app_id, None)
    return out
