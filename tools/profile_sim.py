"""The canned async workload shared by the chip smoke test.

``canned_fixture`` builds a bench_async-style configuration: 600 nodes
in 4 zones, ``m_apps`` apps of ``workers`` workers each, heterogeneous
compute and >= 10% churn, with the ``run_async`` arguments to drive it
(``chip_smoke.py`` phase A).  Where a run's time goes is measured by a
traced benchmark cell (``python3 bench/run.py --workload <cell> --seed
<n> --seconds 30 --trace 1``) and the program's own spans and transfer
counters (``repro.tracing``).
"""
from __future__ import annotations

import os
import sys

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
