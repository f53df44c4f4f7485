"""The benchmark's reduction from a profiler trace to busy time, idle
gaps, kernel time and roofline share (``bench/lib/trace.py``)."""
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench.lib import trace as T  # noqa: E402
from bench.lib.harness import RunData  # noqa: E402
from bench.lib import spec as S_  # noqa: E402
from bench.lib.spec import cell_spec, peaks  # noqa: E402

S = 1e9  # ns per second


def _synthetic():
    """One chip, a 1 s window; operations overlap, one runs past the close."""
    return {
        "device": {"/device:TPU:0": [
            ["a", 0.1 * S, 0.2 * S],
            ["b", 0.2 * S, 0.2 * S],
            ["jit_tree_aggregate_groups/tree_aggregate_groups.1", 0.6 * S, 0.1 * S],
            ["late", 0.95 * S, 0.25 * S],
            ["before", -0.5 * S, 0.3 * S],
        ]},
        "host": [
            ["bench.window", 0.0, 1.0 * S],
            ["bench.apply", 0.02 * S, 0.88 * S],
            ["bench.train", 0.4 * S, 0.15 * S],
        ],
    }


def test_reduce_synthetic_trace_exact():
    r = T.reduce(_synthetic())
    assert r.chips == 1
    assert r.window_s == pytest.approx(1.0)
    # union [0.1, 0.4] + [0.6, 0.7] + [0.95, 1.0]; "before" lies outside
    assert r.busy_s == pytest.approx(0.45)
    assert r.ops == pytest.approx({"a": 0.2, "b": 0.2, "jit_tree_aggregate_groups/tree_aggregate_groups.1": 0.1,
                                   "late": 0.05})
    # gaps: [0, 0.1] and [0.7, 0.95] inside apply, [0.4, 0.6] inside train
    assert r.idle == pytest.approx({"apply": 0.35, "train": 0.2})
    assert sum(r.idle.values()) + r.busy_s == pytest.approx(r.window_s)
    assert [label for _, label in r.gaps] == ["apply", "train", "apply"]
    b = r.breakdown()
    assert b["device_ops"][0][1] == pytest.approx(0.2)
    assert b["idle_gaps"][0] == ["apply", pytest.approx(0.35)]
    assert r.kernel_seconds(S_.kernel("tree_aggregate").TRACE) == pytest.approx(0.1)


def test_reduce_outside_apply_and_two_chips():
    t = _synthetic()
    t["host"] = [["bench.window", 0.0, 1.0 * S]]
    t["device"]["/device:TPU:1"] = [["a", 0.0, 1.0 * S]]
    r = T.reduce(t)
    assert r.chips == 2
    assert r.busy_s == pytest.approx((0.45 + 1.0) / 2)
    assert r.idle == pytest.approx({T.OUTSIDE: 0.55 / 2})


def test_reduce_needs_window_and_device():
    t = _synthetic()
    t["host"] = t["host"][1:]
    with pytest.raises(ValueError, match="bench.window"):
        T.reduce(t)
    with pytest.raises(ValueError, match="device plane"):
        T.reduce({"device": {}, "host": _synthetic()["host"]})


def _recorded():
    path = os.path.join(ROOT, "bench", "testdata", "trace_p22m_qsgd.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_reduce_recorded_chip_trace():
    """A window traced on one TPU v5 lite: four apps with 87 MB payloads,
    qsgd both ways (a cell of an earlier draft of the benchmark)."""
    rec = _recorded()
    r = T.reduce(rec["trace"])
    assert r.chips == 1
    assert 0.0 < r.busy_s <= r.window_s
    assert sum(r.idle.values()) + r.busy_s == pytest.approx(r.window_s, rel=1e-9)
    assert r.window_s == pytest.approx(rec["expected"]["window_s"], rel=1e-9)
    assert r.busy_s == pytest.approx(rec["expected"]["busy_s"], rel=1e-9)
    for kernel, seconds in rec["expected"]["kernel_s"].items():
        pattern = __import__(f"bench.kernels.{kernel}", fromlist=["TRACE"]).TRACE
        assert r.kernel_seconds(pattern) == pytest.approx(seconds, rel=1e-9)


def test_roofline_from_recorded_trace_is_a_share():
    """The recorded window's kernel calls against its kernel time: a
    share of the roofline, above 0 and never past 100%."""
    rec = _recorded()
    spec = cell_spec("fedavg-mnist-2nn.m16")
    calls = [(k, t, tuple((tuple(a[0]), a[1]) if a else None for a in args), kw)
             for k, t, args, kw in rec["kernel_calls"]]
    run = RunData(spec=spec, peaks=peaks("TPU v5 lite"), window_s=rec["expected"]["window_s"],
                  applies=[], spans=[], events=0, compiles=0, kernel_calls=calls,
                  trace=T.reduce(rec["trace"]))
    for kernel in ("tree_aggregate", "qsgd_quantize"):
        share = run.roofline(kernel)
        assert share is not None and 0.0 < share <= 100.0, (kernel, share)
        assert run.bound(kernel) == "bytes"
    run.trace = None
    assert run.roofline("tree_aggregate") is None
