"""The deployment one cell runs, made from the seed.

Copied in spirit from the repository's canned fixtures
(``benchmarks/common.build_system``, ``benchmarks/bench_async._make_apps``,
``tools/profile_sim.canned_fixture``) and kept here, so that the
yardstick cannot move with the program:

- an edge population of ``nodes`` nodes in ``zones`` zones, bulk-joined
  with seeded coordinates and uplink bandwidths;
- ``apps`` concurrent FL apps, each a dataflow tree over ``workers``
  randomly chosen nodes (apps share nodes);
- every worker holds exactly ``shard`` samples of a synthetic
  classification task, a few classes each (FedAvg's pathological
  non-IID split).  Equal shard sizes give every seed the same amount of
  work;
- every app's MLP is drawn on the device in one jitted call.

Every draw derives from ``--seed`` and a fixed salt per purpose, so the
same seed gives the same deployment, data, weights, compute speeds,
churn and rounding bits.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import numpy as np

from bench.lib.spec import Spec, mlp_shapes

# salts: one independent stream per purpose
_OVERLAY, _PLACEMENT, _WEIGHTS, _DATA, _COMPUTE, _CHURN, _ROUNDING, _FOLLOW = range(8)


def sub_seed(seed: int, salt: int, *more: int) -> int:
    """A 31-bit seed for one purpose, from any whole-number ``seed``."""
    ss = np.random.SeedSequence([int(seed) % 2**64, salt, *more])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


def init_params(seed: int, model: dict, n_apps: int) -> list[dict]:
    """Every app's MLP weights, on the device, in one jitted call:
    weights N(0, 1/fan_in), biases zero, float32."""
    import jax
    import jax.numpy as jnp

    shapes = mlp_shapes(model)

    @partial(jax.jit, static_argnums=(1,))
    def draw(key, n):
        out = []
        for k in jax.random.split(key, n):
            ks = jax.random.split(k, 3)
            p = {}
            for i, name in enumerate(("w1", "w2", "w3")):
                shape = shapes[name]
                p[name] = jax.random.normal(ks[i], shape, jnp.float32) / math.sqrt(shape[0])
                b = "b" + name[1]
                p[b] = jnp.zeros(shapes[b], jnp.float32)
            out.append(p)
        return out

    return draw(jax.random.key(sub_seed(seed, _WEIGHTS)), n_apps)


def app_data(seed: int, app: int, model: dict, workers: int, shard: int,
             label_shards: int, centre_scale: float) -> list[tuple[np.ndarray, np.ndarray]]:
    """One app's data: ``workers`` shards of ``shard`` samples each, in
    FedAvg's pathological non-IID split: each worker holds
    ``label_shards`` distinct classes, ``shard / label_shards`` samples
    of each.  Class centres N(0, centre_scale**2 I); a sample is its
    centre plus N(0, I) noise."""
    d, c, k = int(model["dim"]), int(model["classes"]), int(label_shards)
    rng = np.random.default_rng(sub_seed(seed, _DATA, app))
    centres = (rng.standard_normal((c, d), dtype=np.float32) * np.float32(centre_scale))
    classes = np.stack([rng.choice(c, size=k, replace=False) for _ in range(workers)])
    y = np.repeat(classes, -(-shard // k), axis=1)[:, :shard].astype(np.int32)
    x = rng.standard_normal((workers, shard, d), dtype=np.float32)
    x += centres[y]
    return [(x[i], y[i]) for i in range(workers)]


@dataclass
class Deployment:
    """What ``async_engine.run_async`` is called with, plus what the
    reference needs to follow an app without asking the program."""

    system: object
    apps: list
    run_kwargs: dict
    params0: list            # the initial weights the benchmark drew
    data: list               # per app: {worker node: (x, y)}
    policy_seed: int          # roots the commit and broadcast rounding keys


def policy_kwargs(traffic: dict) -> dict:
    c = traffic["compression"]
    return dict(
        kind=c["commit"], levels=int(c.get("levels", 127)), chunk=256,
        downlink=c["broadcast"], downlink_levels=int(c.get("broadcast_levels", 7)),
        chain_cap=int(c.get("chain_cap", 3)),
    )


def follow_apps(seed: int, eligible: list[int], n_follow: int) -> list[int]:
    """``n_follow`` of the ``eligible`` apps (sorted), drawn from the seed."""
    rng = np.random.default_rng(sub_seed(seed, _FOLLOW))
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
        seed=sub_seed(seed, _OVERLAY),
    )
    rng = np.random.default_rng(sub_seed(seed, _PLACEMENT))
    sites = rng.integers(0, zones, n_nodes)
    coords = rng.uniform(0.0, 100.0, (n_nodes, 2))
    lo, hi = cfg["uplink_mbps"]
    bandwidth = rng.uniform(float(lo), float(hi), n_nodes)
    nodes = system.overlay.join_many(sites, coords=coords, bandwidth=bandwidth).tolist()

    n_apps, n_workers = int(tr["apps"]), int(cfg["workers_per_app"])
    params0 = init_params(seed, spec.model, n_apps)
    apps, data = [], []
    for a in range(n_apps):
        workers = [int(n) for n in rng.choice(nodes, size=n_workers, replace=False)]
        handle = system.CreateTree(f"{spec.name}-app{a}")
        system.SubscribeMany(handle.app_id, workers)
        shards = app_data(seed, a, spec.model, n_workers, int(cfg["shard"]),
                          int(cfg["label_shards"]), float(cfg["centre_scale"]))
        by_worker = dict(zip(workers, shards))
        data.append(by_worker)
        apps.append(rounds.FLApp(
            name=handle.name, handle=handle, params=params0[a], model="mlp",
            local_steps=int(cfg["local_steps"]), lr=float(cfg["lr"]), mu=0.0,
            data=by_worker,
        ))

    policy_seed = sub_seed(seed, _ROUNDING)
    policy = CompressionPolicy(seed=policy_seed, **policy_kwargs(tr))
    compute = cfg["compute"]
    churn = cfg["churn"]
    run_kwargs = dict(
        buffer_k=int(cfg["buffer_k"]),
        staleness_alpha=float(cfg["staleness_alpha"]),
        model_bytes=4.0 * spec.n_params,
        compute_ms=async_engine.worker_compute_fn(
            float(compute["base_ms"]), float(compute["spread"]), seed=sub_seed(seed, _COMPUTE)),
        churn=ChurnModel(
            period_ms=float(churn["period_ms"]), downtime_ms=float(churn["downtime_ms"]),
            group_size=int(churn["group_size"]), seed=sub_seed(seed, _CHURN),
        ),
        compression=policy,
    )
    return Deployment(
        system=system, apps=apps, run_kwargs=run_kwargs, params0=params0, data=data,
        policy_seed=policy_seed,
    )
