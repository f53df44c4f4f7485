"""One run of one cell: set-up, the measured window, the reference, the
result line.

``run_cell`` builds the cell's deployment from the seed, drives
``async_engine.run_async`` over it with the probes of ``probe.py``
installed, warms up until at least ``warm_applies`` applies have run and
the last ``quiet_applies`` built no program, measures the next
``seconds`` (whole applies), and, with
``trace``, records a profiler trace of exactly that window and turns the
program's own spans and counters (``repro.tracing``) on for it.  Once the
window has closed and the device's peak memory is read, the program's
state is freed (all but the kind's frozen weights, which the reference
reads too) and the reference follows the apps drawn as the window
opened through every apply up to the last of the window; the numbers of
``compare.py``, and the count of applies whose record disagreed with the
benchmark's own bookkeeping, against the cell's limits decide
``correct``.
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from bench.lib import compare, fixture, probe, reference, spec as spec_mod
from bench.lib import trace as trace_mod
from bench.lib.spec import BENCH, Spec

CACHE_DIR = BENCH / ".cache" / "jax"
TRACE_DIR = BENCH / ".cache" / "trace"


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell needs."""


def device_info(chips: int, check: bool = True) -> dict:
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if check:
        if d0.platform != "tpu":
            raise NoChip(f"no TPU: jax.devices()[0].platform is {d0.platform!r}")
        if len(devices) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
        from repro.kernels import ops

        if ops.kernel_mode() == "jnp":
            raise NoChip("REPRO_KERNEL_MODE=jnp turns the Pallas kernels off")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}


def configure_cache() -> None:
    """JAX's persistent compile cache, at a fixed path in the checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak() -> int | None:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclass
class RunData:
    """What the per-layer readers (``bench/metrics/*.py``) read."""

    spec: Spec
    peaks: dict | None
    window_s: float
    applies: list          # (app, t0, t1, arrivals, loss), in the window
    spans: list            # (name, t0, t1), in the window
    events: int            # scheduler events dispatched in the window
    compiles: int          # XLA programs built in the window
    kernel_calls: list     # (kernel, t, arg shapes, kwargs), in the window
    trace: trace_mod.Reduced | None = None
    # the program's spans in a traced window, (name, t0, t1, parent,
    # self_s), and its counters' growth over the window; None where the
    # run was not traced or the program has no ``repro.tracing``
    program: list | None = None
    counters: dict | None = None

    @property
    def n(self) -> int:
        return len(self.applies)

    def span_s(self, *names: str) -> float:
        return sum(t1 - t0 for name, t0, t1 in self.spans if name in names)

    def per_apply_ms(self, *names: str) -> float | None:
        return 1e3 * self.span_s(*names) / self.n if self.n else None

    def self_ms_per_apply(self, *names: str) -> float | None:
        """Self time of the program's spans ``names``, per apply (ms);
        ``None`` where none of them ran."""
        if self.program is None or not self.n:
            return None
        own = [s[4] for s in self.program if s[0] in names]
        return 1e3 * sum(own) / self.n if own else None

    def counted_per_apply(self, name: str) -> float | None:
        """Growth of the program's counter ``name`` over the window, per apply."""
        if self.counters is None or name not in self.counters or not self.n:
            return None
        return self.counters[name] / self.n

    def train_flops(self) -> float:
        """Operations of the local training the window's applies
        required: per commit, as the model's kind counts them."""
        per_commit = self.spec.kind.train_flops(self.spec.model, self.spec.config)
        return float(sum(a[3] for a in self.applies) * per_commit)

    def kernel_cost(self, kernel: str) -> tuple[float, float, int]:
        """(operations, bytes, calls) of the window's calls of one kernel."""
        km = spec_mod.kernel(kernel)
        flops = nbytes = 0.0
        calls = 0
        for name, _t, shapes, kw in self.kernel_calls:
            if name == km.CALL:
                f, b = km.cost(shapes, kw)
                flops, nbytes, calls = flops + f, nbytes + b, calls + 1
        return flops, nbytes, calls

    def roofline(self, kernel: str) -> float | None:
        """Least time the chip could take for the window's calls of
        ``kernel`` over their device time, in percent; ``None`` where
        the kernel did not run or the trace does not show it."""
        if self.trace is None or self.peaks is None:
            return None
        km = spec_mod.kernel(kernel)
        total = 0.0
        for name, _t, shapes, kw in self.kernel_calls:
            if name == km.CALL:
                f, b = km.cost(shapes, kw)
                total += max(f / self.peaks["flops_per_s"], b / self.peaks["hbm_bytes_per_s"])
        device_s = self.trace.kernel_seconds(km.TRACE)
        if total <= 0.0 or device_s <= 0.0:
            return None
        return 100.0 * total / device_s

    def bound(self, kernel: str) -> str | None:
        f, b, calls = self.kernel_cost(kernel)
        if not calls or self.peaks is None:
            return None
        return ("operations" if f / self.peaks["flops_per_s"] > b / self.peaks["hbm_bytes_per_s"]
                else "bytes")


@dataclass
class Outcome:
    result: dict
    numbers: dict
    # what the reference and its control variants need (bench/control.py)
    replay: dict = field(default_factory=dict)
    run: RunData | None = None
    setup: dict = field(default_factory=dict)  # where set-up went
    window: dict = field(default_factory=dict)  # where the window's stalls came from


def _finite(x) -> float | None:
    return float(x) if x is not None and math.isfinite(x) else None


def _drive(spec: Spec, dep, rec: probe.Recorder) -> None:
    from repro.fl import async_engine

    with probe.installed(rec, dep.system):
        try:
            async_engine.run_async(dep.system, dep.apps, applies=10**9, max_events=10**12,
                                   **dep.run_kwargs)
        except probe.WindowClosed:
            return
    raise RuntimeError("run_async returned before the window closed")


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, log: probe.CompileLog, *,
             t_start: float, check_device: bool = True) -> Outcome:
    device = device_info(spec.chips, check=check_device)
    peaks = spec_mod.peaks(device["kind"]) if check_device else None
    dep = fixture.build(spec, seed)
    t_built = time.perf_counter()

    tracing: dict = {}
    prog = probe.program_tracing() if trace else None

    def on_open():
        if trace:
            import jax

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            tracing["window"] = jax.profiler.TraceAnnotation(trace_mod.WINDOW)
            tracing["window"].__enter__()
            if prog is not None:
                tracing["first"], tracing["counters"] = len(prog.records), prog.snapshot()
                prog.enable()

    def on_close():
        if trace:
            import jax

            if prog is not None:
                prog.disable()
                c0 = tracing["counters"]
                tracing["counters"] = {k: v - c0.get(k, 0) for k, v in prog.snapshot().items()}
            tracing["window"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    n_follow = min(int(spec.cell["follow_apps"]), len(dep.apps))
    rec = probe.Recorder(
        seconds=float(seconds), warm_applies=int(spec.cell["warm_applies"]),
        follow=n_follow, pick=lambda eligible: fixture.follow_apps(seed, eligible, n_follow),
        quiet_applies=int(spec.cell.get("quiet_applies", 0)), builds=lambda: len(log.builds),
        annotate=bool(trace), on_open=on_open, on_close=on_close,
    )
    gclog = probe.GcLog()
    try:
        _drive(spec, dep, rec)
    finally:
        gclog.close()
        if prog is not None:
            prog.disable()
    w = rec.window
    window_s = w.t_close - w.t_open
    device["memory_peak_bytes"] = memory_peak()

    # the program's results for the followed apps, through the last apply
    # of the window, on the host; then free its state
    follow = list(rec.followed)
    program = {}
    for a in follow:
        params, losses, held = rec.program(a)
        program[a] = ([None if p is None else probe.host_copy(p) for p in params], losses,
                      [None if h is None else probe.host_copy(h) for h in held])
    replay = dict(
        follow=follow,
        schedule={a: rec.schedule[a] for a in follow},
        params0={a: probe.host_copy(dep.params0[a]) for a in follow},
        data={a: dep.data[a] for a in follow}, policy_seed=dep.policy_seed,
        shared=dep.shared,  # stays on the device: one copy for every followed app
    )
    mismatches = len(rec.mismatches)
    applies = list(rec.applies)
    spans = [s for s in rec.spans if w.t_open <= s[1] and s[2] <= w.t_close]
    run = RunData(
        spec=spec, peaks=peaks, window_s=window_s, applies=applies, spans=spans,
        events=w.events_close - w.events_open,
        compiles=len(log.between(w.t_open, w.t_close)), kernel_calls=list(rec.kernel_calls),
    )
    if prog is not None:
        run.program = probe.program_spans(prog.records, tracing["first"], w.t_open, w.t_close)
        run.counters = tracing["counters"]
        prog.clear()
    setup_s = w.t_open - t_start
    setup = {"build_s": t_built - t_start, "warm_s": w.t_open - t_built,
             "warm_applies": len(rec.warm_marks),
             "warm_builds": len(log.between(t_start, w.t_open)),
             "followed": {a: len(rec.schedule[a]) for a in follow}}
    window = {"compiles": run.compiles, **stalls(applies, gclog.between(w.t_open, w.t_close))}
    del dep, rec
    gc.collect()

    numbers = readings(spec, replay, program)["program"]
    numbers["schedule_mismatches"] = float(mismatches)
    limits = {k: float(v) for k, v in spec.cell["limits"].items()}
    correct = compare.judge(numbers, limits)

    # an apply that aggregated nothing (its commits drained by churn) or
    # whose loss is not finite did not complete
    failed = sum(1 for a in applies if a[3] == 0 or not math.isfinite(a[4]))
    metrics: dict = {}
    units = {m["name"]: m["unit"] for m in spec.end_to_end + spec.per_layer}
    if trace:
        if check_device:
            raw = trace_mod.read_xplane(trace_mod.find_xplane(str(TRACE_DIR)))
            run.trace = trace_mod.reduce(raw)
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
        for m in spec.per_layer:
            value = spec_mod.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        e2e = {
            "setup_s": setup_s,
            "applies_per_s": (len(applies) - failed) / window_s,
            "apply_p95_ms": (1e3 * float(np.percentile([t1 - t0 for _, t0, t1, _, _ in applies],
                                                       95)) if applies else None),
        }
        for m in spec.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": units[m["name"]]}
    result = {
        "correct": bool(correct),
        "attempted": len(applies),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        result["breakdown"] = run.trace.breakdown()
    # a number that is missing or not finite prints as null (strict JSON)
    result["checks"] = {k: {"value": _finite(numbers.get(k)), "limit": limits[k]} for k in limits}
    return Outcome(result=result, numbers=numbers, replay=replay, run=run, setup=setup,
                   window=window)


def stalls(applies: list, collections: list) -> dict:
    """What held the window's applies up: their spread, the longest time
    between two applies, and Python's garbage collections."""
    ms = sorted(1e3 * (t1 - t0) for _, t0, t1, _, _ in applies)
    gaps = [1e3 * (b[1] - a[2]) for a, b in zip(applies, applies[1:])]
    full = [s for _, s, g in collections if g == 2]
    return {
        "apply_ms_median": ms[len(ms) // 2] if ms else None, "apply_ms_max": ms[-1] if ms else None,
        "applies_over_2x_median": sum(1 for m in ms if m > 2 * ms[len(ms) // 2]) if ms else 0,
        "gap_ms_max": max(gaps, default=0.0),
        "gc_s": sum(s for _, s, _ in collections), "gc_full": len(full),
        "gc_full_ms_max": 1e3 * max(full, default=0.0),
    }


def readings(spec: Spec, replay: dict, program: dict | None, modes=()) -> dict:
    """The cell's numbers against the sound reference: of ``program``
    (per followed app: params, losses and held state per apply) under
    ``"program"``, and of the reference run in each of ``modes`` and put
    in the program's place (the control and the faults)."""
    broadcast = spec.traffic["compression"]["broadcast"] != "none"
    names = (["program"] if program is not None else []) + list(modes)
    per_app: dict = {n: [] for n in names}
    for a in replay["follow"]:
        schedule = replay["schedule"][a]
        if len(schedule) < probe.FOLLOWED_APPLIES:
            return {n: {"followed_applies": float("nan")} for n in names}
        kw = dict(app=a, params0=replay["params0"][a], data=replay["data"][a],
                  schedule=schedule, config=spec.config, traffic=spec.traffic,
                  policy_seed=replay["policy_seed"], shared=replay["shared"])
        sound = reference.follow(mode="sound", **kw)
        ref = (sound.params, sound.losses, sound.held)
        for n in names:
            if n == "program":
                got = program[a]
            else:
                r = reference.follow(mode=n, **kw)
                got = (r.params, r.losses, r.held)
            per_app[n].append(compare.app_numbers(replay["params0"][a], got, ref, broadcast))
    return {n: compare.cell_numbers(v) for n, v in per_app.items()}


def print_result(result: dict) -> None:
    """The checks as the last lines of standard error, the result as
    the last line of standard output."""
    import json

    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(args, t_start: float) -> int:
    try:
        spec = spec_mod.cell_spec(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        device_info(spec.chips)
    except NoChip as e:
        print(f"bench: {e}; this benchmark runs only on the chip", file=sys.stderr)
        return 3
    configure_cache()
    log = probe.CompileLog()
    outcome = run_cell(spec, args.seed, args.seconds, bool(args.trace), log, t_start=t_start)
    print("setup: " + " ".join(f"{k}={v!r}" for k, v in outcome.setup.items()), file=sys.stderr)
    print("window: " + " ".join(f"{k}={v!r}" for k, v in outcome.window.items()), file=sys.stderr)
    print_result(outcome.result)
    return 0
