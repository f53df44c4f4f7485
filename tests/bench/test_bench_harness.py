"""Rehearsals of the benchmark harness on the CPU, at a size a test can
hold: it refuses to report without a chip, a sound run comes out
correct, and the control (the reference one precision down, put in the
program's place) fails the cell's limits."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench.lib import compare, harness, probe  # noqa: E402
from bench.lib import spec as S  # noqa: E402

CELL = "fedavg-mnist-2nn.m16"


def _run_py(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", str(2**31 + 7),
         "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240,
    )


@pytest.mark.parametrize("trace", ["0", "1"])
def test_refuses_without_a_chip(trace):
    p = _run_py(ROOT, "--trace", trace)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "runs only on the chip" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run_py(tmp_path, "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("quiet,builds,opens_at", [
    (0, [1, 2, 3, 4, 5, 6], 3),        # warm_applies alone
    (2, [1, 2, 3, 3, 3, 3], 5),        # then two applies with no build
    (2, [1, 2, 3, 4, 5, 6, 6, 6], 8),  # builds went on past warm_applies
])
def test_warm_up_waits_for_quiet_applies(quiet, builds, opens_at):
    clock = iter(range(1000))
    seen = iter(builds)
    rec = probe.Recorder(seconds=1.0, warm_applies=3, quiet_applies=quiet,
                         builds=lambda: next(seen), clock=lambda: float(next(clock)) * 1e-3)
    for n in range(1, len(builds) + 1):
        rec.n_applied[0] = n
        if rec.warm():
            break
    assert n == opens_at


# each cell's rehearsal size: two apps on 48 nodes, the model at its
# published widths, a fifth of each worker's shard
CELLS = ["fedavg-mnist-2nn.m16", "fedavg-mnist-2nn.m64"]


@pytest.fixture(scope="module", params=CELLS)
def tiny(request):
    return S.shrunk(S.cell_spec(request.param), nodes=48, apps=2, warm_applies=4, shard=120)


@pytest.fixture(scope="module")
def sound_run(tiny):
    return harness.run_cell(tiny, 2**31 + 11, 1.0, False, probe.CompileLog(),
                            t_start=time.perf_counter(), check_device=False)


def test_sound_run_is_correct_and_well_formed(sound_run, tiny):
    r = sound_run.result
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == set(tiny.cell["limits"])
    assert {m["name"] for m in tiny.end_to_end} == set(r["metrics"])
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["device"]["platform"] == "cpu"
    # every number compared is finite and inside its limit on the CPU;
    # the first three applies, which the replay meets before any lattice
    # step can round the other way, well inside
    for name, c in r["checks"].items():
        assert 0.0 <= c["value"] <= c["limit"], name
        if not name.startswith("window_"):
            assert c["value"] <= 0.1 * c["limit"], name


def test_followed_apps_are_drawn_among_those_with_three_applies(sound_run, tiny):
    """However unequal the apps' rates, every followed app has its first
    three applies to compare, and the draw takes as many as the cell asks."""
    follow = sound_run.replay["follow"]
    assert len(follow) == min(tiny.cell["follow_apps"], tiny.traffic["apps"])
    for a in follow:
        assert len(sound_run.replay["schedule"][a]) >= probe.FOLLOWED_APPLIES


def test_untraced_run_leaves_the_program_tracer_off(sound_run):
    """With ``--trace 0`` the program's spans and counters stay off, so
    they cannot move an end-to-end reading; their readers find nothing."""
    from repro import tracing

    assert sound_run.run.program is None and sound_run.run.counters is None
    assert tracing._on is False and tracing.records == []
    assert S.reader("quantize_ms_per_apply")(sound_run.run) is None


def test_control_fails_the_limits(sound_run, tiny):
    """The cell's control (the reference one precision down, put in the
    program's place on the same schedule) fails its limits."""
    control = tiny.cell["control"]
    got = harness.readings(tiny, sound_run.replay, None, [control])[control]
    limits = {k: v for k, v in tiny.cell["limits"].items() if k in got}
    assert not compare.judge(got, limits), got
