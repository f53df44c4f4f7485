"""FL round orchestration over the simulated Totoro+ overlay.

Drives full FedAvg/FedProx rounds for paper-scale models through the
Table-II API: Broadcast the global model down the dataflow tree, workers
run E local steps on their (non-IID) shards, model deltas aggregate up
the tree level-by-level (internal nodes run the ``tree_aggregate``
kernel's math), the master applies the server update and replicates its
state to the k-node neighborhood set.

Also provides ``CentralizedBaseline``: the OpenFL/FedScale-style single
coordinator that serves M concurrent applications through one queue —
the queuing behavior behind the paper's Table III speedups.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np

from repro.core.api import TotoroSystem
from repro.fl import engine
from repro.fl import small_models as sm


@dataclass
class FLApp:
    name: str
    handle: object
    params: object
    model: str = "mlp"
    local_steps: int = 4
    lr: float = 0.1
    mu: float = 0.0  # FedProx
    data: dict = field(default_factory=dict)  # node -> (x, y)
    round_num: int = 0
    history: list = field(default_factory=list)


def make_app(
    system: TotoroSystem,
    name: str,
    *,
    workers: list[int],
    data_by_worker: dict,
    model: str = "mlp",
    dim: int = 32,
    hidden: int = 64,
    num_classes: int = 8,
    local_steps: int = 4,
    lr: float = 0.1,
    mu: float = 0.0,
    seed: int = 0,
) -> FLApp:
    handle = system.CreateTree(name)
    for w in workers:
        system.Subscribe(handle.app_id, w)
    if model == "mlp":
        params = sm.init_mlp(jax.random.key(seed), dim, hidden, num_classes)
    else:
        params = sm.init_cnn(jax.random.key(seed), num_classes)
    return FLApp(
        name=name, handle=handle, params=params, model=model,
        local_steps=local_steps, lr=lr, mu=mu, data=data_by_worker,
    )


def run_round(system: TotoroSystem, app: FLApp, *, use_kernel: bool = True) -> dict:
    """One Totoro+ round through the Table-II verbs; returns metrics incl.
    modeled wall time.

    Broadcast down the tree, every worker's local steps in one engine
    dispatch (``engine.fused_local_training``, a batch of one job),
    hierarchical kernel aggregation up the tree (``TotoroSystem.Aggregate``
    executes the level schedule), master server-update + state
    replication.
    """
    return run_round_fused(system, [app], use_kernel=use_kernel)[0]


def run_round_fused(system: TotoroSystem, apps: list[FLApp], *, use_kernel: bool = True) -> list[dict]:
    """One round for MANY apps with a single fused training dispatch.

    Every app Broadcasts, then all apps' workers train together through
    ``engine.fused_local_training`` (same-config apps stack into one
    megabatched vmap; deltas unstack per app), then each app Aggregates
    and applies its server update.  Per app this is ``run_round``;
    dispatches per round are the number of distinct static configs.
    Returns one metrics dict per app, in ``apps`` order.
    """
    bstats_all, jobs = [], []
    for app in apps:
        bstats_all.append(system.Broadcast(app.handle.app_id, app.params))
        workers = [w for w in sorted(app.handle.tree.members) if w in app.data]
        jobs.append((app, workers, app.params))
    trained = engine.fused_local_training(jobs)

    out = []
    for app, bstats, (_, workers, _), (deltas, weights, losses) in zip(
        apps, bstats_all, jobs, trained
    ):
        astats = system.Aggregate(
            app.handle.app_id,
            {w: d for w, d in zip(workers, deltas)},
            weights={w: wt for w, wt in zip(workers, weights)},
            use_kernel=use_kernel,
        )
        agg = astats["result"]
        app.params = jax.tree.map(
            lambda p, d: (p + d).astype(p.dtype), app.params, agg
        )
        app.round_num += 1
        system.replicate_master_state(app.handle.app_id, {"round": app.round_num})
        metrics = {
            "round": app.round_num,
            "loss": float(np.mean(losses)) if losses else 0.0,
            "time_ms": bstats["time_ms"] + astats["time_ms"],
            "traffic_bytes": bstats["bytes"] + astats["bytes"],
            "agg_levels": astats.get("levels", []),
        }
        app.history.append(metrics)
        out.append(metrics)
    return out


def run_async(
    system: TotoroSystem,
    apps: list[FLApp],
    *,
    applies: int,
    buffer_k: int | list[int],
    staleness_alpha: float = 0.5,
    model_bytes: float,
    compute_ms=50.0,
    churn=None,
    barrier: bool = False,
    adaptive: bool = False,
    adaptive_kwargs: dict | None = None,
    selector=None,
    app_weights=None,
    app_rate_caps=None,
    relay_admission=None,
) -> dict:
    """FedBuff-style buffered-async rounds on the event clock.

    Delegates to ``fl/async_engine.run_async``: every worker's
    download / compute / upload is its own simulator event, the master
    applies a staleness-weighted update after ``buffer_k`` arrivals
    (``CommitDelta``/``ApplyBuffered`` verbs), and optional ``churn``
    (``core.sim.ChurnModel``) fails/rejoins workers mid-round.
    ``adaptive=True`` re-sizes K per apply (``core.sim
    .AdaptiveKController``); ``selector`` plugs in utility-based client
    admission (``fl/selection``).  Transfers are priced by the
    weighted-fair flow engine; ``app_weights`` / ``app_rate_caps`` /
    ``relay_admission`` expose the per-app fairness knobs.
    """
    from repro.fl import async_engine

    return async_engine.run_async(
        system, apps, applies=applies, buffer_k=buffer_k,
        staleness_alpha=staleness_alpha, model_bytes=model_bytes,
        compute_ms=compute_ms, churn=churn, barrier=barrier,
        adaptive=adaptive, adaptive_kwargs=adaptive_kwargs, selector=selector,
        app_weights=app_weights, app_rate_caps=app_rate_caps,
        relay_admission=relay_admission,
    )


def evaluate(app: FLApp, x, y) -> float:
    return float(sm.accuracy(sm.LOGITS[app.model](app.params, x), y))


# ---------------------------------------------------------------------------
# centralized baseline (OpenFL / FedScale architecture)


@dataclass
class CentralizedBaseline:
    """Single coordinator, first-come-first-served across M applications
    (paper §VII-D: 'the central coordinator needs to handle them one by
    one ... which causes large queuing delays')."""

    server_bandwidth_mbps: float = 1000.0
    coordinator_overhead_ms: float = 20.0

    def round_time_ms(self, apps: list[FLApp], per_round_compute_ms: float, model_bytes: float) -> list[float]:
        """Per-app wall time for one round of every app: uploads/downloads
        serialize through the central server's link + coordinator queue."""
        times = []
        clock = 0.0
        for app in apps:
            n_workers = max(len(app.data), 1)
            xfer_ms = 2 * n_workers * model_bytes * 8 / (self.server_bandwidth_mbps * 1e3)
            clock += self.coordinator_overhead_ms + xfer_ms + per_round_compute_ms
            times.append(clock)
        return times
