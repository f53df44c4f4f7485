"""Named host spans and host<->device transfer counters for the data plane.

Off by default.  Off, ``span`` returns one shared no-op context manager
after a single module-level flag check, ``pull`` / ``push`` are
``np.asarray`` / ``jnp.asarray`` and ``implicit_push`` returns its
argument.  On (``enable``), every span records
``[name, t0, t1, parent, attrs]`` into ``records`` on the
``time.perf_counter`` clock; ``parent`` is the index in ``records`` of
the innermost span open when it began (``-1`` for none), so a span's
self time is its duration less its children's.  Each span on also opens
a ``jax.profiler.TraceAnnotation("totoro.<name>")``, which puts it on a
running profiler's clock beside the device's operations.

Span names used by the program:

- ``event``: one event callback of ``core.sim.EventCore.run_events``;
- ``apply`` (app, version): one buffered apply, ``AsyncTrainer.apply``;
- ``replicate``: master-state replication at the end of an apply;
- ``train``, ``train.pack``: ``engine.fused_local_training``, and its
  host packing and upload of shards and start params;
- ``quantize``: ``quantize_delta`` / ``quantize_broadcast_delta``;
- ``verb.commit``, ``verb.apply``: ``CommitDelta`` / ``ApplyBuffered``;
- ``broadcast``, ``chain``: the downlink state build and its chain apply;
- ``xfer.d2h``: one blocking device-to-host copy (``pull``), including
  the wait for the program that produces it.

``counters`` are monotone: device arrays copied to the host
(``d2h_pulls``, ``d2h_bytes``), host values uploaded, explicitly by
``push`` or inside the jax operation ``implicit_push`` hands them to
(``h2d_pushes``, ``h2d_bytes``), quantize calls that ran as the grid
program and one kernel dispatch (``quantize_fused``) or took an eager
branch (``quantize_eager``), ``ApplyBuffered`` aggregations that took the
compiled quantized path (``apply_fused``) or another (``apply_eager``),
the buffered commits that path took as grids already on the device
(``grid_resident``) or had to upload (``grid_uploaded``), and broadcast
states built on the device (``broadcast_fused``) or on the host
(``broadcast_eager``); they move only while tracing is on.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

_on = False
records: list[list] = []  # [name, t0, t1, parent, attrs]
_open: list[int] = []     # indices in ``records`` of the spans open now
counters = {
    "d2h_pulls": 0, "d2h_bytes": 0, "h2d_pushes": 0, "h2d_bytes": 0,
    "quantize_fused": 0, "quantize_eager": 0,
    "apply_fused": 0, "apply_eager": 0, "broadcast_fused": 0, "broadcast_eager": 0,
    "grid_resident": 0, "grid_uploaded": 0,
}


class _Off:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("rec", "i", "ann")

    def __init__(self, name: str, attrs: dict):
        self.rec = [name, 0.0, None, -1, attrs]

    def __enter__(self):
        self.ann = jax.profiler.TraceAnnotation("totoro." + self.rec[0])
        self.ann.__enter__()
        self.i = len(records)
        self.rec[3] = _open[-1] if _open else -1
        records.append(self.rec)
        _open.append(self.i)
        self.rec[1] = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.rec[2] = time.perf_counter()
        if self.i in _open:  # spans left open inside this one close with it
            del _open[_open.index(self.i):]
        self.ann.__exit__(*exc)
        return False


def span(name: str, **attrs):
    """A context manager timing ``name``; the shared no-op while off."""
    if not _on:
        return _OFF
    return _Span(name, attrs)


def enable() -> None:
    """Record spans, annotate a running ``jax.profiler`` trace with them
    and count transfers, from now on."""
    global _on
    _on = True


def disable() -> None:
    """Stop recording.  Spans open now still close and record their end."""
    global _on
    _on = False


def snapshot() -> dict:
    """The counters now (window deltas are differences of two)."""
    return dict(counters)


def clear() -> None:
    """Forget the recorded spans; only while none is open."""
    if _open:
        raise RuntimeError(f"{len(_open)} span(s) still open")
    records.clear()


def count(name: str) -> None:
    """Add one to ``counters[name]`` while tracing is on."""
    if _on:
        counters[name] += 1


def host_cached(x) -> bool:
    """Whether ``np.asarray(x)`` moves nothing: ``x`` is no device array,
    or its host copy already exists (jax keeps the copy of its first
    conversion in ``_npy_value``)."""
    return not isinstance(x, jax.Array) or getattr(x, "_npy_value", None) is not None


def pull(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``; while on, a real device-to-host copy is
    timed as span ``xfer.d2h`` and counted with its bytes."""
    if not _on or host_cached(x):
        return np.asarray(x, dtype)
    with span("xfer.d2h"):
        out = np.asarray(x, dtype)
    counters["d2h_pulls"] += 1
    counters["d2h_bytes"] += int(x.nbytes)
    return out


def push(x, dtype=None):
    """``jnp.asarray(x, dtype)``, an explicit upload; while on, an upload
    of a host value is counted with the bytes it takes on the device."""
    out = jnp.asarray(x, dtype)
    if _on and not isinstance(x, jax.Array):
        counters["h2d_pushes"] += 1
        counters["h2d_bytes"] += int(out.nbytes)
    return out


def implicit_push(x):
    """``x`` itself, handed to a jax operation that uploads it; while on,
    a host value is counted as one upload of its bytes.  The upload stays
    inside the operation's call: an explicit ``jnp.asarray`` in front of
    it would cost more host time than the call's own transfer."""
    if _on and not isinstance(x, jax.Array):
        counters["h2d_pushes"] += 1
        counters["h2d_bytes"] += int(np.asarray(x).nbytes)
    return x
