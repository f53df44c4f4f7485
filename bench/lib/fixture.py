"""The deployment one cell runs, made from the seed.

Copied in spirit from the repository's canned fixtures
(``benchmarks/common.build_system``, ``benchmarks/bench_async._make_apps``,
``tools/profile_sim.canned_fixture``) and kept here, so that the
yardstick cannot move with the program:

- an edge population of ``nodes`` nodes in ``zones`` zones, bulk-joined
  with seeded coordinates and uplink bandwidths;
- ``apps`` concurrent FL apps, each a dataflow tree over ``workers``
  randomly chosen nodes (apps share nodes);
- every worker holds exactly ``shard`` samples, drawn by the model's
  kind (``bench/models/<kind>.py``).  Equal shard sizes give every seed
  the same amount of work;
- every app's weights are drawn by the kind on the device in one jitted
  call;
- where the kind has a frozen part that every app shares (``shared``),
  it is drawn once, and the one copy is handed to every app through the
  kind's ``program_fields``.

Every draw derives from ``--seed`` and a fixed salt per purpose, so the
same seed gives the same deployment, data, weights, compute speeds,
churn and rounding bits.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from bench.lib.spec import Spec

# salts: one independent stream per purpose; a new one is appended, so
# the streams before it keep their bits
OVERLAY, PLACEMENT, WEIGHTS, DATA, COMPUTE, CHURN, ROUNDING, FOLLOW, SHARED = range(9)


def sub_seed(seed: int, salt: int, *more: int) -> int:
    """A 31-bit seed for one purpose, from any whole-number ``seed``."""
    ss = np.random.SeedSequence([int(seed) % 2**64, salt, *more])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


@dataclass
class Deployment:
    """What ``async_engine.run_async`` is called with, plus what the
    reference needs to follow an app without asking the program."""

    system: object
    apps: list
    run_kwargs: dict
    params0: list            # the initial weights the benchmark drew
    data: list               # per app: {worker node: shard}
    policy_seed: int          # roots the commit and broadcast rounding keys
    shared: object = None     # the kind's frozen weights, one copy for all apps


def policy_kwargs(traffic: dict) -> dict:
    c = traffic["compression"]
    return dict(
        kind=c["commit"], levels=int(c.get("levels", 127)), chunk=256,
        downlink=c["broadcast"], downlink_levels=int(c.get("broadcast_levels", 7)),
        chain_cap=int(c.get("chain_cap", 3)),
    )


def follow_apps(seed: int, eligible: list[int], n_follow: int) -> list[int]:
    """``n_follow`` of the ``eligible`` apps (sorted), drawn from the seed."""
    rng = np.random.default_rng(sub_seed(seed, FOLLOW))
    n = min(n_follow, len(eligible))
    return sorted(int(a) for a in rng.choice(eligible, size=n, replace=False)) if n else []


def build(spec: Spec, seed: int) -> Deployment:
    """The whole deployment of ``spec`` for ``seed``: population, trees,
    apps with data and weights, compute and churn models, compression."""
    from repro.core.api import TotoroSystem
    from repro.core.sim import ChurnModel
    from repro.fl import async_engine, rounds
    from repro.fl.compression import CompressionPolicy

    cfg, tr = spec.config, spec.traffic
    n_nodes, zones = int(cfg["nodes"]), int(cfg["zones"])
    system = TotoroSystem(
        zone_bits=int(math.log2(zones)), suffix_bits=24, base_bits=4,
        seed=sub_seed(seed, OVERLAY),
    )
    rng = np.random.default_rng(sub_seed(seed, PLACEMENT))
    sites = rng.integers(0, zones, n_nodes)
    coords = rng.uniform(0.0, 100.0, (n_nodes, 2))
    lo, hi = cfg["uplink_mbps"]
    bandwidth = rng.uniform(float(lo), float(hi), n_nodes)
    nodes = system.overlay.join_many(sites, coords=coords, bandwidth=bandwidth).tolist()

    kind = spec.kind
    n_apps, n_workers = int(tr["apps"]), int(cfg["workers_per_app"])
    params0 = kind.init_params(sub_seed(seed, WEIGHTS), spec.model, n_apps)
    shared, fields = None, {}
    if hasattr(kind, "shared"):
        shared = kind.shared(sub_seed(seed, SHARED), spec.model)
        if hasattr(kind, "program_fields"):
            fields = kind.program_fields(shared)
    apps, data = [], []
    for a in range(n_apps):
        workers = [int(n) for n in rng.choice(nodes, size=n_workers, replace=False)]
        handle = system.CreateTree(f"{spec.name}-app{a}")
        system.SubscribeMany(handle.app_id, workers)
        shards = kind.app_data(seed, a, cfg, n_workers)
        by_worker = dict(zip(workers, shards))
        data.append(by_worker)
        apps.append(rounds.FLApp(
            name=handle.name, handle=handle, params=params0[a], model=kind.PROGRAM,
            local_steps=int(cfg["local_steps"]), lr=float(cfg["lr"]), mu=0.0,
            data=by_worker, **fields,
        ))

    policy_seed = sub_seed(seed, ROUNDING)
    policy = CompressionPolicy(seed=policy_seed, **policy_kwargs(tr))
    compute = cfg["compute"]
    churn = cfg["churn"]
    run_kwargs = dict(
        buffer_k=int(cfg["buffer_k"]),
        staleness_alpha=float(cfg["staleness_alpha"]),
        model_bytes=float(cfg["model_bytes"]),
        compute_ms=async_engine.worker_compute_fn(
            float(compute["base_ms"]), float(compute["spread"]), seed=sub_seed(seed, COMPUTE)),
        churn=ChurnModel(
            period_ms=float(churn["period_ms"]), downtime_ms=float(churn["downtime_ms"]),
            group_size=int(churn["group_size"]), seed=sub_seed(seed, CHURN),
        ),
        compression=policy,
    )
    return Deployment(
        system=system, apps=apps, run_kwargs=run_kwargs, params0=params0, data=data,
        policy_seed=policy_seed, shared=shared,
    )
