"""From a profiler trace to device busy time, idle gaps and kernel time.

``read_xplane`` keeps two kinds of events of a ``jax.profiler`` trace:

- device operations: every event on the ``XLA Ops`` line of each
  ``/device:...`` plane (one plane per chip), named by the program it
  ran in and its HLO instruction (``jit_subtract/sub.1``);
- host spans: the benchmark's ``TraceAnnotation``s (names starting
  ``bench.``) on the host plane, among them ``bench.window`` around the
  whole measured window, and the program's own (``totoro.``, from
  ``repro.tracing`` while the window is traced).

Both are on the profiler's one clock, in nanoseconds.  ``reduce`` then
works on that plain list, which is also what ``bench/testdata`` keeps:

- busy: the union of a chip's operation intervals inside the window,
  averaged over the chips;
- idle gaps: the complement, each labelled with the innermost
  benchmark span covering its middle (``outside apply`` when none does,
  that is while the event core and the scheduler run) and, where one
  covers it, the innermost program span (``aggregate/xfer.d2h``);
- kernel time: the summed durations of the operations whose name a
  kernel's pattern matches.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

HOST_PREFIX = "bench."
PROGRAM_PREFIX = "totoro."
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OUTSIDE = "outside apply"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _module(name: str) -> str:
    """``jit_subtract(1234...)`` -> ``jit_subtract``."""
    return re.sub(r"\(\d+\)$", "", name)


def _instruction(name: str) -> str:
    """``%sub.1 = (f32[...]) subtract(...)`` -> ``sub.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str) -> dict:
    """The device operations and benchmark host spans of one trace, as
    ``{"device": {plane: [[name, start_ns, dur_ns], ...]}, "host": [...]}``.
    An operation is named ``<module>/<instruction>``: the jitted program
    it ran in (from the ``XLA Modules`` line) and its HLO instruction."""
    import bisect

    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, _module(e.name))
                          for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in mods]
            ops = device.setdefault(plane.name, [])
            for e in lines[OPS_LINE]:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and e.start_ns <= mods[i][1] else "?"
                ops.append([f"{mod}/{_instruction(e.name)}", float(e.start_ns),
                            float(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith((HOST_PREFIX, PROGRAM_PREFIX)))
    return {"device": device, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclass
class Reduced:
    window_s: float
    busy_s: float                     # averaged over the chips
    chips: int
    ops: dict[str, float]             # operation name -> seconds, all chips
    idle: dict[str, float]            # benchmark span -> idle seconds, chip average
    gaps: list[tuple[float, str]]     # (seconds, benchmark span), longest first, chip 0
    # "<benchmark span>/<program span>" (or the benchmark span alone where
    # no program span covers the gap) -> idle seconds, chip average
    idle_by_span: dict[str, float] = field(default_factory=dict)

    def kernel_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for name, s in self.ops.items() if rx.search(name))

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in idle]}


def _innermost(points: list[float], spans: list[tuple[str, float, float]]) -> list[str | None]:
    """For each of the ascending ``points``, the name of the shortest of
    the ``spans`` (which nest, as one thread's spans do) that covers it,
    or ``None``."""
    order = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, stack, i = [], [], 0
    for p in points:
        while i < len(order) and order[i][1] <= p:
            while stack and stack[-1][2] < order[i][1]:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and stack[-1][2] < p:
            stack.pop()
        out.append(stack[-1][0] if stack else None)
    return out


def reduce(trace: dict) -> Reduced:
    """Busy time, idle gaps and per-operation time inside the window."""
    windows = [(s, s + d) for name, s, d in trace["host"] if name == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW} span")
    lo, hi = windows[0]
    spans = {prefix: [(name[len(prefix):], s, s + d) for name, s, d in trace["host"]
                      if name.startswith(prefix) and name != WINDOW]
             for prefix in (HOST_PREFIX, PROGRAM_PREFIX)}
    chips = sorted(trace["device"])
    if not chips:
        raise ValueError("the trace has no device plane with an XLA Ops line")
    busy, ops, idle, by_span, gaps0 = 0.0, {}, {}, {}, []
    for i, chip in enumerate(chips):
        inside = []
        for name, s, d in trace["device"][chip]:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                inside.append((a, b))
                ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
        merged = _union(inside)
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        mids = [0.5 * (a + b) for a, b in gaps]
        bench = _innermost(mids, spans[HOST_PREFIX])
        program = _innermost(mids, spans[PROGRAM_PREFIX])
        for (a, b), label, inner in zip(gaps, bench, program):
            label = label or OUTSIDE
            both = label if inner is None else f"{label}/{inner}"
            idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9 / len(chips)
            by_span[both] = by_span.get(both, 0.0) + (b - a) * 1e-9 / len(chips)
            if i == 0:
                gaps0.append(((b - a) * 1e-9, label))
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / len(chips), chips=len(chips),
        ops=ops, idle=idle, gaps=sorted(gaps0, reverse=True), idle_by_span=by_span,
    )
