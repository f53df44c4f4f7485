"""What the benchmark records around one ``run_async`` call.

Nothing here goes inside the program: the probes are wrappers, installed
from the benchmark's own files for the duration of one run and removed
after it, around the calls into each layer:

- ``AsyncTrainer.apply`` (span ``apply``): one buffered apply, which
  also opens and closes the measured window;
- ``engine.fused_local_training`` (span ``train``);
- ``quantize_delta`` and ``TotoroSystem.CommitDelta`` (span ``commit``);
- ``TotoroSystem.ApplyBuffered`` and ``AsyncTrainer._broadcast_state``
  (span ``aggregate``);
- the ``CALL`` of every cost model under ``bench/kernels/``, in its
  ``MODULE`` (``repro.kernels.ops`` unless the cost model says
  otherwise): the shapes each kernel is called with, for the roofline
  readers (no timing);
- ``AsyncTrainer.begin_download`` / ``commit`` / ``drop``: the
  benchmark's own bookkeeping of which commit trained from which
  version, for the reference, and against which every apply's record
  (version, commits, staleness, simulated time) is checked.  This
  bookkeeping runs inside the program's own span ``bench``
  (``repro.tracing``), so that the program's spans do not count it as
  theirs.

The window opens at the end of an apply once at least ``warm_applies``
applies have run, ``follow`` apps have their first three applies in, and
the last ``quiet_applies`` applies built no program (``CompileLog``), or
at the latest ``WARM_CAP_S`` after the run began.  It closes at the end
of the first apply that ends ``seconds`` later, so it holds whole
applies and all the time between them.

The apps the reference follows are drawn from the seed as the window
opens, among those whose first three applies are in: apps apply at rates
that differ many times over (a slow app may not reach its third apply
for minutes), so apps drawn before the run could have nothing to compare.
Until then every app's first three and latest applies are kept as host
copies (set-up time, no device memory); from then on only the followed
apps' are kept, as references, through the last apply of the window.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import importlib
import time
from dataclasses import dataclass, field

import numpy as np

FOLLOWED_APPLIES = 3  # compared at each end: the first three and the last three in the window
KEPT_LAST = FOLLOWED_APPLIES + 1  # the window's numbers start from the state before its last three
WARM_CAP_S = 120.0  # the longest warm-up before the window opens regardless


class WindowClosed(Exception):
    """Raised from inside the apply probe to end ``run_async``."""


class CompileLog:
    """XLA programs built in this process, from JAX's monitoring events
    (each program compiled or loaded from the persistent cache)."""

    _BUILD = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.builds: list[tuple[float, float, str]] = []  # (time, seconds, name)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, secs, fun_name="", **_):
        if event == self._BUILD:
            self.builds.append((time.perf_counter(), secs, fun_name))

    def between(self, t0: float, t1: float) -> list[tuple[float, float, str]]:
        return [b for b in self.builds if t0 <= b[0] <= t1]


class GcLog:
    """Python's garbage collections in this process: (start, seconds,
    generation) of each, to tell a host stall of the collector from one of
    the machine."""

    def __init__(self):
        self.runs: list[tuple[float, float, int]] = []
        self._t0: float | None = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.runs.append((self._t0, time.perf_counter() - self._t0, info["generation"]))
            self._t0 = None

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def between(self, t0: float, t1: float) -> list[tuple[float, float, int]]:
        return [r for r in self.runs if t0 <= r[0] <= t1]


@dataclass
class Window:
    t_open: float | None = None
    t_close: float | None = None
    events_open: int = 0
    events_close: int = 0


def host_copy(tree):
    """A pytree of arrays, copied to the host."""
    import jax

    return jax.tree.map(np.asarray, tree)


def program_tracing():
    """The program's ``repro.tracing`` module, or ``None`` where the
    program has none."""
    try:
        return importlib.import_module("repro.tracing")
    except ModuleNotFoundError as e:
        if e.name not in ("repro", "repro.tracing"):
            raise
        return None


def program_spans(records: list, first: int, t_open: float, t_close: float) -> list:
    """The program's span records from index ``first`` on, as
    ``(name, t0, t1, parent, self_s)``: times cut to the window, parents
    renumbered from ``first`` (``-1`` for a parent outside), and the self
    time, the duration less that of the span's children."""
    out = []
    for name, t0, t1, parent, _attrs in records[first:]:
        t0, t1 = max(t0, t_open), min(t1, t_close)
        out.append([name, t0, max(t1, t0), parent - first if parent >= first else -1, 0.0])
    for s in out:
        s[4] += s[2] - s[1]
        if s[3] >= 0:
            out[s[3]][4] -= s[2] - s[1]
    return [tuple(s) for s in out]


@dataclass
class Recorder:
    """Spans, apply records, kernel calls and the schedule of one run."""

    seconds: float
    warm_applies: int
    follow: int = 0           # apps the reference follows, chosen as the window opens
    pick: object = None       # (eligible apps, sorted) -> the followed ones
    quiet_applies: int = 0
    builds: object = None    # () -> programs built so far in this process
    annotate: bool = False
    on_open: object = None   # called just before the window opens
    on_close: object = None  # called just after it closes
    clock: object = time.perf_counter
    spans: list = field(default_factory=list)        # (name, t0, t1)
    applies: list = field(default_factory=list)      # (app, t0, t1, arrivals, loss)
    kernel_calls: list = field(default_factory=list)  # (kernel, t, args)
    window: Window = field(default_factory=Window)
    scheduler: object = None
    # the benchmark's own version bookkeeping, per app
    n_applied: dict = field(default_factory=dict)
    base: dict = field(default_factory=dict)
    pending: dict = field(default_factory=dict)
    seq: dict = field(default_factory=dict)
    # per app (every app in warm-up, the followed ones in the window): the
    # commits of each apply (worker, base version, commit number), its
    # loss, and the weights and held state after its first three and its
    # last ``KEPT_LAST`` applies
    followed: list = field(default_factory=list)
    schedule: dict = field(default_factory=dict)
    losses: dict = field(default_factory=dict)
    first: dict = field(default_factory=dict)
    last: dict = field(default_factory=dict)
    held: dict = field(default_factory=dict)
    # applies in the window whose record disagrees with the benchmark's
    # own bookkeeping: (app, what), and the last simulated time seen
    mismatches: list = field(default_factory=list)
    t_sim: float = float("-inf")

    def __post_init__(self):
        self.t_begin = self.clock()
        self.warm_marks: list[int] = []  # programs built by the end of each warm apply

    def eligible(self) -> list[int]:
        """Apps whose first three applies are in."""
        return sorted(a for a, f in self.first.items() if len(f) >= FOLLOWED_APPLIES)

    def warm(self) -> bool:
        """Whether warm-up is over: enough applies, ``follow`` apps with
        their first three applies in, and no build in the last
        ``quiet_applies`` applies."""
        self.warm_marks.append(self.builds() if self.builds is not None else 0)
        if self.clock() - self.t_begin > WARM_CAP_S:
            return True
        if sum(self.n_applied.values()) < self.warm_applies:
            return False
        if len(self.eligible()) < self.follow:
            return False
        q, marks = self.quiet_applies, self.warm_marks
        return q == 0 or (len(marks) > q and marks[-1] == marks[-1 - q])

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = self.clock()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        else:
            yield
        self.spans.append((name, t0, self.clock()))

    def check(self, ai: int, n: int, t: float, commits, record) -> None:
        """The apply's record against the benchmark's own count: the
        version it produced, the commits it aggregated, their mean
        staleness, and a simulated clock that never runs back."""
        stale = [n - 1 - base for _, base, _ in commits]
        if int(record["version"]) != n:
            self.mismatches.append((ai, "version"))
        if int(record["arrivals"]) != len(commits):
            self.mismatches.append((ai, "arrivals"))
        if stale and abs(float(record["mean_staleness"]) - sum(stale) / len(stale)) > 1e-9:
            self.mismatches.append((ai, "staleness"))
        if float(t) < self.t_sim:
            self.mismatches.append((ai, "clock"))
        self.t_sim = max(self.t_sim, float(t))

    def keep(self, ai: int, n: int, params: dict, held: dict, commits, loss: float,
             host: bool) -> None:
        """Record apply ``n`` of app ``ai``; in warm-up (``host``) as host
        copies, so that what is kept for apps that end up not followed
        never holds device memory."""
        if host:
            same = held is params
            params = host_copy(params)
            held = params if same else host_copy(held)
        self.schedule.setdefault(ai, []).append(list(commits))
        self.losses.setdefault(ai, []).append(loss)
        if n <= FOLLOWED_APPLIES:
            self.first.setdefault(ai, []).append((params, held))
        self.last.setdefault(ai, collections.deque(maxlen=KEPT_LAST)).append((n - 1, params, held))

    def choose(self) -> None:
        """Draw the followed apps among the eligible ones, drop the rest."""
        eligible = self.eligible()
        self.followed = list(self.pick(eligible)) if self.pick else eligible[:self.follow]
        for kept in (self.schedule, self.losses, self.first, self.last):
            for a in [a for a in kept if a not in self.followed]:
                del kept[a]

    def program(self, ai: int) -> tuple[list, list, list]:
        """What the program produced for a followed app: per apply its
        weights, loss and held state, ``None`` where it was not kept."""
        n = len(self.schedule[ai])
        params, held = [None] * n, [None] * n
        for i, (p, h) in enumerate(self.first[ai]):
            params[i], held[i] = p, h
        for i, p, h in self.last[ai]:
            params[i], held[i] = p, h
        return params, list(self.losses[ai]), held

    # -- the apply probe ---------------------------------------------------------

    def after_apply(self, trainer, ai: int, t0: float, t1: float, commits, record,
                    t_sim: float = 0.0) -> None:
        w = self.window
        if record is not None:
            n = self.n_applied.get(ai, 0) + 1
            self.n_applied[ai] = n
            if w.t_open is not None:
                self.check(ai, n, t_sim, commits, record)
            if w.t_open is None or ai in self.followed:
                params = trainer.apps[ai].params  # immutable: a reference, no copy
                self.keep(ai, n, params, self.held.get(ai, params), commits,
                          float(record["loss"]), host=w.t_open is None)
            self.held.pop(ai, None)
            arrivals, loss = int(record["arrivals"]), float(record["loss"])
        else:
            arrivals, loss = 0, float("nan")
        if w.t_open is None:
            if self.warm():
                self.choose()
                if self.on_open is not None:
                    self.on_open()
                w.events_open = self.scheduler.events_dispatched
                w.t_open = self.clock()
            return
        self.applies.append((ai, t0, t1, arrivals, loss))
        if t1 - w.t_open >= self.seconds:
            w.t_close = t1
            w.events_close = self.scheduler.events_dispatched
            if self.on_close is not None:
                self.on_close()
            raise WindowClosed


def _shape(x):
    """(shape, bytes per element) of an array argument, else ``None``."""
    if not hasattr(x, "shape") or not hasattr(x, "dtype"):
        return None
    return tuple(int(d) for d in x.shape), int(x.dtype.itemsize)


@contextlib.contextmanager
def installed(rec: Recorder, system):
    """Install every probe for one run; restore the program on exit."""
    from bench.lib.spec import kernel, kernels
    from repro.core import sim
    from repro.fl import async_engine, engine

    tracing = program_tracing()
    trainer_cls = async_engine.AsyncTrainer
    saved = []

    def patch(owner, name, make):
        own = vars(owner).get(name)  # None: inherited (a bound method)
        saved.append((owner, name, own))
        setattr(owner, name, make(getattr(owner, name)))

    def spanned(label):
        def make(orig):
            def wrapper(*a, **kw):
                with rec.span(label):
                    return orig(*a, **kw)
            return wrapper
        return make

    def shapes(kernel):
        def make(orig):
            def wrapper(*a, **kw):
                if rec.window.t_open is not None and rec.window.t_close is None:
                    rec.kernel_calls.append(
                        (kernel, rec.clock(), tuple(_shape(x) for x in a),
                         dict(kw)))
                return orig(*a, **kw)
            return wrapper
        return make

    def apply_probe(orig):
        def apply(self, ai, t, **kw):
            commits = rec.pending.get(ai, [])
            rec.pending[ai] = []
            t0 = rec.clock()
            with rec.span("apply"):
                record = orig(self, ai, t, **kw)
            t1 = rec.clock()
            with tracing.span("bench") if tracing else contextlib.nullcontext():
                rec.after_apply(self, ai, t0, t1, commits, record, t_sim=t)
            return record
        return apply

    def begin_download_probe(orig):
        def begin_download(self, ai, w):
            rec.base.setdefault(ai, {})[w] = rec.n_applied.get(ai, 0)
            return orig(self, ai, w)
        return begin_download

    def commit_probe(orig):
        def commit(self, ai, w, t):
            s = rec.seq.get(ai, 0)
            rec.seq[ai] = s + 1
            rec.pending.setdefault(ai, []).append((w, rec.base[ai].pop(w), s))
            return orig(self, ai, w, t)
        return commit

    def drop_probe(orig):
        def drop(self, ai, w):
            rec.base.get(ai, {}).pop(w, None)
            return orig(self, ai, w)
        return drop

    def broadcast_probe(orig):
        def broadcast_state(self, ai, params, version, policy):
            with rec.span("aggregate"):
                held = orig(self, ai, params, version, policy)
            rec.held[ai] = held
            return held
        return broadcast_state

    def run_probe(orig):
        def run(self, *a, **kw):
            rec.scheduler = self
            return orig(self, *a, **kw)
        return run

    try:
        patch(trainer_cls, "apply", apply_probe)
        patch(trainer_cls, "begin_download", begin_download_probe)
        patch(trainer_cls, "commit", commit_probe)
        patch(trainer_cls, "drop", drop_probe)
        patch(trainer_cls, "_broadcast_state", broadcast_probe)
        patch(sim.AsyncBufferScheduler, "run", run_probe)
        patch(engine, "fused_local_training", spanned("train"))
        patch(async_engine, "quantize_delta", spanned("commit"))
        patch(system, "CommitDelta", spanned("commit"))
        patch(system, "ApplyBuffered", spanned("aggregate"))
        calls = {(getattr(km, "MODULE", "repro.kernels.ops"), km.CALL)
                 for km in map(kernel, kernels())}
        for module, call in sorted(calls):
            patch(importlib.import_module(module), call, shapes(call))
        yield rec
    finally:
        for owner, name, own in reversed(saved):
            if own is None:
                delattr(owner, name)
            else:
                setattr(owner, name, own)
