"""Find everything that belongs to one cell by its name.

``BENCHMARK.json`` at the checkout root names each cell's configuration
and traffic mix.  The files they point to:

- ``configs[].file``: the deployment (population, dataflow trees, model,
  FL algorithm settings);
- ``bench/traffic/<traffic>.json``: the mix of concurrent apps and the
  compression they use;
- ``bench/cells/<cell>.json``: what decides ``correct`` in this cell
  (apps followed by the reference, the limit on each number compared);
- ``bench/metrics/<metric>.py``: one reader per per-layer metric;
- ``bench/kernels/<kernel>.py``: one cost model per kernel;
- ``bench/peaks.json``: the chip's published peaks, keyed by
  ``device_kind``.

A later cell, configuration or metric is added by adding such files.
"""
from __future__ import annotations

import importlib
import json
import math
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
    def n_params(self) -> int:
        return sum(math.prod(s) for s in mlp_shapes(self.model).values())


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


def shrunk(spec: Spec, *, nodes: int, apps: int, warm_applies: int, hidden: int | None = None,
           shard: int | None = None) -> Spec:
    """The same cell with fewer nodes, apps and warm-up applies, and
    optionally a narrower hidden layer and fewer samples per worker: the
    size at which its rehearsals run on a CPU in the tests."""
    from dataclasses import replace

    model = {**spec.model, **({"hidden": hidden} if hidden else {})}
    config = {**spec.config, "nodes": nodes, "model": model,
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


def mlp_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    """Leaf shapes of the apps' MLP (two hidden layers, ReLU)."""
    d, h, c = int(model["dim"]), int(model["hidden"]), int(model["classes"])
    return {
        "w1": (d, h), "b1": (h,),
        "w2": (h, h), "b2": (h,),
        "w3": (h, c), "b3": (c,),
    }


def mlp_flops_per_sample(model: dict) -> int:
    """Matmul operations of one local SGD step on one sample: forward
    2 MACs per weight; backward the weight gradients of all three layers
    and the input gradients of layers 2 and 3 (the data needs none).
    Biases and activations are left out."""
    d, h, c = int(model["dim"]), int(model["hidden"]), int(model["classes"])
    weights = d * h + h * h + h * c
    return 2 * weights + 2 * weights + 2 * (h * h + h * c)
