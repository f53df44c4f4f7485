"""The harness drives a whole run with the timed path broken underneath
(no chip; the CPU at a test's size) and ``correct`` comes out false for
each fault the cells can have."""
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench.lib import harness, probe  # noqa: E402
from bench.lib import spec as S  # noqa: E402


def _frozen(monkeypatch):
    """An apply that returns its state unchanged."""
    from repro.core.api import TotoroSystem

    orig = TotoroSystem.ApplyBuffered

    def apply_buffered(self, *a, **kw):
        stats = orig(self, *a, **kw)
        if stats["result"] is not None:
            stats["result"] = jax.tree.map(np.zeros_like, stats["result"])
        return stats

    monkeypatch.setattr(TotoroSystem, "ApplyBuffered", apply_buffered)


def _half(monkeypatch):
    """Half of each worker's batch left out, the mean taken over the rest."""
    from repro.fl import engine

    orig = engine.megabatched_local_train

    def train(params, x, y, mask, **kw):
        m = np.array(mask)
        for row in m:
            real = int(row.sum())
            row[real // 2:] = 0.0
        return orig(params, x, y, jnp.asarray(m), **kw)

    monkeypatch.setattr(engine, "megabatched_local_train", train)


def _altered(monkeypatch):
    """One commit altered where it is produced: its update negated."""
    from repro.fl import engine

    orig = engine.fused_local_training

    def fused(jobs, **kw):
        out = list(orig(jobs, **kw))
        deltas, weights, losses = out[0]
        out[0] = ([jax.tree.map(lambda a: -a, deltas[0])] + list(deltas[1:]), weights, losses)
        return out

    monkeypatch.setattr(engine, "fused_local_training", fused)


@pytest.mark.parametrize("cell", ["fedavg-mnist-2nn.m16", "fedavg-mnist-2nn.m64"])
@pytest.mark.parametrize("fault", [_frozen, _half, _altered], ids=["frozen", "half", "altered"])
def test_fault_makes_correct_false(monkeypatch, fault, cell):
    # two apps on 48 nodes, at a width and shard a CPU test holds
    spec = S.shrunk(S.cell_spec(cell), nodes=48, apps=2, warm_applies=2, hidden=64, shard=120)
    fault(monkeypatch)
    out = harness.run_cell(spec, 2**31 + 99, 0.5, False, probe.CompileLog(),
                           t_start=time.perf_counter(), check_device=False)
    assert out.result["correct"] is False, out.result["checks"]
