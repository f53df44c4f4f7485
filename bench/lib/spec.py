"""Find everything that belongs to one cell by its name.

``BENCHMARK.json`` at the checkout root names each cell's configuration
and traffic mix.  The files they point to:

- ``configs[].file``: the deployment (population, dataflow trees, model,
  FL algorithm settings);
- ``bench/models/<kind>.py``: the model that the configuration's
  ``model.kind`` names: its trained shapes, weights, any frozen part
  its apps share, data, plain loss for the reference, operation count
  and CPU-rehearsal size
  (``bench/models/__init__.py`` gives the contract);
- ``bench/traffic/<traffic>.json``: the mix of concurrent apps and the
  compression they use;
- ``bench/cells/<cell>.json``: what decides ``correct`` in this cell
  (apps followed by the reference, the limit on each number compared);
- ``bench/metrics/<metric>.py``: one reader per per-layer metric;
- ``bench/kernels/<kernel>.py``: one cost model per kernel; the probes
  record the calls of every one found there;
- ``bench/peaks.json``: the chip's published peaks, keyed by
  ``device_kind``.

A later cell, configuration or metric is added by adding such files.
"""
from __future__ import annotations

import importlib
import json
import math
import pkgutil
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


@dataclass(frozen=True)
class Spec:
    """One cell with everything it is built from."""

    name: str
    chips: int
    config: dict
    traffic: dict
    cell: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def kind(self):
        return model_kind(self.model)

    @property
    def n_params(self) -> int:
        return n_params(self.model)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_spec(name: str, root: Path = ROOT) -> Spec:
    """The cell ``name`` of ``BENCHMARK.json`` with its files loaded."""
    bm = benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    cell = load_json(root / "bench" / "cells" / f"{name}.json")
    return Spec(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        cell=cell,
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, name)],
    )


def shrunk(spec: Spec, *, nodes: int, apps: int, warm_applies: int, shard: int | None = None,
           **sizes) -> Spec:
    """The same cell with fewer nodes, apps and warm-up applies, and
    optionally fewer samples per worker and a smaller model (``sizes``,
    as the kind's ``shrink`` takes them): the size at which its
    rehearsals run on a CPU in the tests.  The parameter count and the
    priced ``model_bytes`` follow the model."""
    from dataclasses import replace

    model = spec.kind.shrink(spec.model, **sizes)
    n = n_params(model)
    config = {**spec.config, "nodes": nodes, "model": model, "params": n,
              "model_bytes": n * stored_itemsize(spec.config),
              **({"shard": shard} if shard else {})}
    return replace(
        spec, config=config, traffic={**spec.traffic, "apps": apps},
        cell={**spec.cell, "warm_applies": warm_applies},
    )


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """Published peaks of ``device_kind``; a device missing from the
    table is an error, never a default."""
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in bench/peaks.json (have {sorted(table)})"
        )
    return table[device_kind]


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    return importlib.import_module(f"bench.metrics.{metric}").read


def kernel(name: str):
    """The cost model module ``bench/kernels/<name>.py``."""
    return importlib.import_module(f"bench.kernels.{name}")


def model_kind(model: dict):
    """The module ``bench/models/<kind>.py`` of ``model["kind"]``."""
    kind = model["kind"]
    try:
        return importlib.import_module(f"bench.models.{kind}")
    except ModuleNotFoundError as e:
        if e.name != f"bench.models.{kind}":
            raise
        raise KeyError(f"model kind {kind!r} has no module bench/models/{kind}.py") from None


def n_params(model: dict) -> int:
    return sum(math.prod(s) for s in model_kind(model).shapes(model).values())


def stored_itemsize(config: dict) -> int:
    """Bytes per parameter in the configuration's stored ``dtype``."""
    import jax.numpy as jnp

    return int(jnp.dtype(config["dtype"]).itemsize)


def kernels() -> list[str]:
    """The name of every cost model under ``bench/kernels/``."""
    import bench.kernels

    return sorted(m.name for m in pkgutil.iter_modules(bench.kernels.__path__))
