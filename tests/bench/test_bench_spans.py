"""The probes follow the cost models under ``bench/kernels``, and a traced
window hands the program's own spans and counters (``repro.tracing``)
to the per-layer readers, on the CPU at a test's size."""
import math
import os
import random
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

import bench.kernels  # noqa: E402
from bench.lib import harness, probe  # noqa: E402
from bench.lib import spec as S  # noqa: E402
from bench.lib import trace as T  # noqa: E402

# the readers of the program's spans and counters
PROGRAM_READERS = [
    "event_core_ms_per_apply", "bookkeeping_ms_per_apply", "pack_ms_per_apply",
    "quantize_ms_per_apply", "verb_ms_per_apply", "d2h_wait_ms_per_apply",
    "d2h_pulls_per_apply", "d2h_mb_per_apply", "h2d_mb_per_apply", "quantize_fused_share",
]

# two cost models no bench file has: one of a wrapper in kernels.ops (the
# default owner), one of a function in another module of the program
EXTRA_COST_MODELS = {
    "buffered_aggregate_quantized": '''
CALL = "buffered_aggregate_quantized"
TRACE = r"^jit_buffered_aggregate_quantized/"


def cost(args, kwargs):
    return 1.0, 1.0
''',
    "staleness_weights": '''
MODULE = "repro.kernels.tree_aggregate"
CALL = "staleness_weights"
TRACE = r"^jit_staleness_weights/"


def cost(args, kwargs):
    return 1.0, 1.0
''',
}


def _add_cost_models(monkeypatch, directory, models: dict) -> None:
    for name, text in models.items():
        (directory / f"{name}.py").write_text(text)
        monkeypatch.delitem(sys.modules, f"bench.kernels.{name}", raising=False)
    monkeypatch.setattr(bench.kernels, "__path__", [*bench.kernels.__path__, str(directory)])


def test_probes_follow_the_cost_models(tmp_path, monkeypatch):
    owner = types.ModuleType("bench_test_owner")
    owner.double = lambda x, *, k=2: k * x
    monkeypatch.setitem(sys.modules, "bench_test_owner", owner)
    _add_cost_models(monkeypatch, tmp_path, {"double": '''
MODULE = "bench_test_owner"
CALL = "double"
TRACE = r"^jit_double/"


def cost(args, kwargs):
    (shape, item), = args
    return float(shape[0] * shape[1]), float(2 * item * shape[0] * shape[1])
'''})
    assert "double" in S.kernels() and set(S.kernels()) >= {"qsgd_quantize", "tree_aggregate"}
    from repro.kernels import ops

    rec = probe.Recorder(seconds=1.0, warm_applies=0)
    system = types.SimpleNamespace(CommitDelta=lambda *a: None, ApplyBuffered=lambda *a: None)
    original, quantize = owner.double, ops.qsgd_quantize
    with probe.installed(rec, system):
        assert owner.double is not original and ops.qsgd_quantize is not quantize
        owner.double(np.ones((3, 4)))          # window not open: not recorded
        rec.window.t_open = 0.0
        assert owner.double(np.ones((3, 4)), k=3)[0, 0] == 3.0
    assert owner.double is original and ops.qsgd_quantize is quantize
    assert [(k, a, kw) for k, _, a, kw in rec.kernel_calls] == [("double", (((3, 4), 8),), {"k": 3})]
    run = harness.RunData(spec=None, peaks=None, window_s=1.0, applies=[], spans=[], events=0,
                          compiles=0, kernel_calls=rec.kernel_calls)
    assert run.kernel_cost("double") == (12.0, 192.0, 1)


def test_program_spans_renumber_and_self_time():
    # records of a whole run; the window holds those from index 2 on and
    # closes inside the last one
    records = [["event", 0.0, 1.0, -1, {}], ["apply", 0.1, 0.9, 0, {}],
               ["event", 2.0, 3.0, -1, {}], ["apply", 2.1, 2.7, 2, {}],
               ["xfer.d2h", 2.2, 2.4, 3, {}], ["xfer.d2h", 2.8, 2.9, 2, {}],
               ["bench", 3.0, 3.2, 1, {}]]
    spans = probe.program_spans(records, 2, 1.5, 3.1)
    names = [s[0] for s in spans]
    assert names == ["event", "apply", "xfer.d2h", "xfer.d2h", "bench"]
    assert [s[3] for s in spans] == [-1, 0, 1, 0, -1]
    self_s = dict(zip(range(5), (s[4] for s in spans)))
    assert self_s[0] == pytest.approx(1.0 - 0.6 - 0.1)
    assert self_s[1] == pytest.approx(0.6 - 0.2)
    assert self_s[4] == pytest.approx(0.1)      # cut at the window's close
    run = harness.RunData(spec=None, peaks=None, window_s=1.6, applies=[None, None], spans=[],
                          events=0, compiles=0, kernel_calls=[], program=spans,
                          counters={"d2h_pulls": 3, "quantize_fused": 4, "quantize_eager": 0})
    assert run.self_ms_per_apply("xfer.d2h") == pytest.approx(150.0)
    assert run.self_ms_per_apply("train.pack") is None
    assert S.reader("d2h_pulls_per_apply")(run) == 1.5
    assert S.reader("quantize_fused_share")(run) == 100.0
    run.program, run.counters = None, None
    assert all(S.reader(m)(run) is None for m in PROGRAM_READERS)


def _brute_innermost(mid, spans):
    inner = [(b - a, name) for name, a, b in spans if a <= mid <= b]
    return min(inner)[1] if inner else None


def _nested(rng, lo, hi, depth, out):
    t = lo
    while depth and t < hi:
        a = t + rng.random() * (hi - t) * 0.3
        b = a + rng.random() * (hi - a) * 0.6
        out.append((f"s{len(out)}", a, b))
        _nested(rng, a, b, depth - 1, out)
        t = b + rng.random() * 0.01
    return out


@pytest.mark.parametrize("seed", range(5))
def test_innermost_span_matches_the_brute_force(seed):
    rng = random.Random(seed)
    spans = _nested(rng, 0.0, 100.0, 4, [])
    points = sorted(rng.uniform(-1.0, 101.0) for _ in range(400))
    assert T._innermost(points, spans) == [_brute_innermost(p, spans) for p in points]


def test_reduce_labels_gaps_with_program_spans():
    S_ = 1e9
    trace = {
        "device": {"/device:TPU:0": [["a", 0.1 * S_, 0.2 * S_], ["b", 0.44 * S_, 0.16 * S_],
                                     ["c", 0.9 * S_, 0.1 * S_]]},
        "host": [["bench.window", 0.0, 1.0 * S_], ["bench.aggregate", 0.3 * S_, 0.6 * S_],
                 ["totoro.verb.apply", 0.32 * S_, 0.55 * S_],
                 ["totoro.xfer.d2h", 0.36 * S_, 0.06 * S_]],
    }
    r = T.reduce(trace)
    # gaps [0, 0.1] (no span), [0.3, 0.44] (mid 0.37: a pull inside the
    # verb), [0.6, 0.9] (mid 0.75: the verb itself)
    assert r.idle == pytest.approx({T.OUTSIDE: 0.1, "aggregate": 0.44})
    assert r.idle_by_span == pytest.approx(
        {T.OUTSIDE: 0.1, "aggregate/xfer.d2h": 0.14, "aggregate/verb.apply": 0.3})
    assert r.breakdown()["idle_gaps"][0] == ["aggregate/verb.apply", pytest.approx(0.3)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    try:
        _add_cost_models(mp, tmp_path_factory.mktemp("kernels"), EXTRA_COST_MODELS)
        spec = S.shrunk(S.cell_spec("fedavg-mnist-2nn.m16"), nodes=48, apps=2, warm_applies=4,
                        shard=120)
        yield harness.run_cell(spec, 2**31 + 23, 1.0, True, probe.CompileLog(),
                               t_start=time.perf_counter(), check_device=False)
    finally:
        mp.undo()


def test_spans_reach_the_readers(traced):
    run = traced.run
    assert traced.result["correct"] is True and run.n > 0
    names = {s[0] for s in run.program}
    assert names >= {"event", "apply", "train", "train.pack", "quantize", "verb.commit",
                     "verb.apply", "xfer.d2h", "bench"}
    assert all(run.window_s >= s[2] - s[1] >= 0.0 for s in run.program)
    # self times add up to the spans' top-level time, no more
    top = sum(s[2] - s[1] for s in run.program if s[3] == -1)
    assert sum(s[4] for s in run.program) == pytest.approx(top, rel=1e-9, abs=1e-12)
    assert run.counters["d2h_pulls"] > 0 and run.counters["quantize_eager"] == 0
    metrics = traced.result["metrics"]
    for name in PROGRAM_READERS:
        assert math.isfinite(metrics[name]["value"]), name
    assert metrics["quantize_fused_share"]["value"] == 100.0
    from repro import tracing

    assert tracing._on is False and tracing.records == []


def test_added_cost_models_record_calls(traced):
    calls = {k for k, *_ in traced.run.kernel_calls}
    assert {"buffered_aggregate_quantized", "staleness_weights", "qsgd_quantize",
            "tree_aggregate_groups"} <= calls
