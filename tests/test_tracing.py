"""The program's spans and transfer counters (``repro.tracing``): off they
record nothing and change nothing; on they nest, count each real
device-to-host copy once, and leave an async run's results unchanged."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import data as data_mod
from repro import tracing
from repro.core.api import TotoroSystem
from repro.core.sim import ChurnModel
from repro.fl import async_engine, rounds
from repro.fl.compression import CompressionPolicy


@pytest.fixture
def tracer():
    tracing.disable()
    tracing.clear()
    yield tracing
    tracing.disable()
    tracing.clear()


def test_off_records_nothing_and_returns_values_unchanged(tracer):
    before = tracer.snapshot()
    with tracer.span("a", k=1) as s, tracer.span("b"):
        pass
    assert s is tracer.span("c")  # one shared no-op object
    x = jnp.arange(6.0).reshape(2, 3)
    got = tracer.pull(x)
    assert isinstance(got, np.ndarray)
    np.testing.assert_array_equal(got, np.asarray(x))
    assert tracer.pull(x, np.float64).dtype == np.float64
    h = np.arange(4, dtype=np.int8)
    up = tracer.push(h)
    assert isinstance(up, jax.Array) and up.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(up), h)
    assert tracer.implicit_push(h) is h
    assert tracer.records == [] and tracer.snapshot() == before


def test_nested_spans_link_parents_and_give_self_time(tracer):
    tracer.enable()
    with tracer.span("outer", app=3):
        with tracer.span("inner"):
            with tracer.span("leaf"):
                pass
        with tracer.span("inner"):
            pass
    with tracer.span("next"):
        pass
    recs = tracer.records
    assert [r[0] for r in recs] == ["outer", "inner", "leaf", "inner", "next"]
    assert [r[3] for r in recs] == [-1, 0, 1, 0, -1]
    assert recs[0][4] == {"app": 3}
    dur = [r[2] - r[1] for r in recs]
    own = list(dur)
    for r, d in zip(recs, dur):
        if r[3] >= 0:
            own[r[3]] -= d
    assert all(d >= 0 for d in dur)
    assert all(o >= -1e-12 for o in own)
    assert own[0] == pytest.approx(dur[0] - dur[1] - dur[3])
    for child in (1, 3):  # children lie inside their parent
        assert recs[0][1] <= recs[child][1] <= recs[child][2] <= recs[0][2]


def test_pull_counts_a_device_array_once(tracer):
    tracer.enable()
    x = jnp.arange(1000, dtype=jnp.float32) * 2.0
    before = tracer.snapshot()
    a = tracer.pull(x)
    if x._npy_value is None:      # a zero-copy backend caches nothing;
        x._npy_value = a          # a copying one (the TPU) keeps this copy
    b = tracer.pull(x)            # its host copy exists: nothing moves
    c = tracer.pull(np.ones(8))   # a host array is free
    d = tracer.pull([1.0, 2.0])
    after = tracer.snapshot()
    assert after["d2h_pulls"] - before["d2h_pulls"] == 1
    assert after["d2h_bytes"] - before["d2h_bytes"] == 4000
    assert [r[0] for r in tracer.records] == ["xfer.d2h"]
    np.testing.assert_array_equal(a, b)
    assert c.shape == (8,) and d.shape == (2,)


def test_host_copy_check_follows_jax(tracer):
    """jax keeps the host copy of an array's first conversion in
    ``_npy_value`` and hands it back without a transfer; the tracer's
    check reads that attribute (jax is pinned below 0.10)."""
    x = jnp.arange(8.0) + 1.0
    assert x._npy_value is None and not tracer.host_cached(x)
    host = np.arange(8.0, dtype=np.float32) + 1.0
    host.flags.writeable = False
    x._npy_value = host
    assert x._value is host
    assert tracer.host_cached(x) and tracer.host_cached(host)


def test_push_counts_host_bytes_on_the_device(tracer):
    tracer.enable()
    before = tracer.snapshot()
    tracer.push(np.zeros((10, 4), np.float32))
    tracer.push(np.zeros(16, np.int8))
    tracer.push(np.zeros(5, np.float64), jnp.float32)  # counted as uploaded
    tracer.push(jnp.zeros(100))                        # already on the device
    after = tracer.snapshot()
    assert after["h2d_pushes"] - before["h2d_pushes"] == 3
    assert after["h2d_bytes"] - before["h2d_bytes"] == 160 + 16 + 20


def test_implicit_push_counts_the_upload_and_returns_its_argument(tracer):
    tracer.enable()
    before = tracer.snapshot()
    h = np.zeros((3, 256), np.int8)
    assert tracer.implicit_push(h) is h
    y = jnp.ones(8)
    assert tracer.implicit_push(y) is y  # already on the device
    after = tracer.snapshot()
    assert after["h2d_pushes"] - before["h2d_pushes"] == 1
    assert after["h2d_bytes"] - before["h2d_bytes"] == 768


def test_enable_and_disable_inside_an_open_span(tracer):
    with tracer.span("off-outer"):   # entered off: never on the stack
        tracer.enable()
        with tracer.span("a"):
            with tracer.span("b"):
                tracer.disable()      # "b" and "a" still close and record
            with tracer.span("c"):    # off: not recorded
                pass
            tracer.enable()
            with tracer.span("d"):    # "a" is still open: its child
                pass
    with tracer.span("e"):
        pass
    recs = tracer.records
    assert [r[0] for r in recs] == ["a", "b", "d", "e"]
    assert [r[3] for r in recs] == [-1, 0, 0, -1]
    assert all(r[2] is not None and r[2] >= r[1] for r in recs)
    assert tracer._open == []
    tracer.clear()
    assert tracer.records == []


def _deployment(seed=0):
    """Two apps of 6 workers on 120 nodes, qsgd commits, delta-qsgd
    broadcasts, churn: every span of the async data plane runs."""
    sys_ = TotoroSystem(zone_bits=2, suffix_bits=20, seed=seed)
    rng = np.random.default_rng(seed)
    nodes = [sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2)) for i in range(120)]
    apps = []
    for a in range(2):
        x, y = data_mod.synthetic_classification(6 * 40, 16, 4, seed=seed + a)
        parts = np.array_split(np.arange(len(y)), 6)
        ws = [int(w) for w in rng.choice(nodes, size=6, replace=False)]
        apps.append(rounds.make_app(
            sys_, f"app{a}", workers=ws,
            data_by_worker={w: (x[parts[i]], y[parts[i]]) for i, w in enumerate(ws)},
            dim=16, num_classes=4, local_steps=2, lr=0.2, seed=seed + a,
        ))
    return sys_, apps


def _run(seed=0):
    sys_, apps = _deployment(seed)
    res = async_engine.run_async(
        sys_, apps, applies=4, buffer_k=3, model_bytes=1e5,
        compute_ms=async_engine.worker_compute_fn(30.0, 4.0, seed=seed),
        churn=ChurnModel(period_ms=120.0, downtime_ms=240.0, group_size=1, seed=seed),
        compression=CompressionPolicy(kind="qsgd-int8", downlink="delta-qsgd"),
    )
    return res, apps


def test_async_run_is_the_same_with_the_tracer_on(tracer):
    off, apps_off = _run()
    tracer.enable()
    before = tracer.snapshot()
    on, apps_on = _run()
    tracer.disable()
    after = tracer.snapshot()
    assert off["events"] == on["events"]
    assert off["churn"] == on["churn"]
    assert off["history"] == on["history"]
    for a, b in zip(apps_off, apps_on):
        for la, lb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    recs = tracer.records
    names = {r[0] for r in recs}
    assert {"event", "apply", "replicate", "train", "train.pack", "quantize", "verb.commit",
            "verb.apply", "broadcast", "chain", "xfer.d2h"} <= names
    assert sum(r[0] == "apply" for r in recs) == len(on["history"])
    # every device-to-host wait sits inside a named span of the data plane
    for r in recs:
        if r[0] == "xfer.d2h":
            assert r[3] >= 0 and recs[r[3]][0] not in ("event", "xfer.d2h"), recs[r[3]][0]
    assert after["d2h_pulls"] > before["d2h_pulls"]
    assert after["h2d_bytes"] > before["h2d_bytes"]
    assert tracer._open == []


def test_every_quantize_of_a_run_is_one_grid_program_and_one_kernel(tracer):
    """With the benchmark's policy (qsgd-int8 commits, delta-qsgd
    broadcasts) every commit and every broadcast quantize is fused."""
    tracer.enable()
    before = tracer.snapshot()
    res, _ = _run()
    tracer.disable()
    after = tracer.snapshot()
    calls = sum(r[0] == "quantize" for r in tracer.records)
    commits = sum(h["arrivals"] for h in res["history"])
    assert calls == commits + len(res["history"]) > 0
    assert after["quantize_fused"] - before["quantize_fused"] == calls
    assert after["quantize_eager"] == before["quantize_eager"]


def test_delta_qsgd_apply_keeps_the_aggregate_step_on_the_device(tracer):
    """One apply of qsgd commits with a delta-qsgd broadcast: the compiled
    apply and broadcast paths engage once each, ``verb.apply``,
    ``broadcast`` and ``chain`` pull twice between them (the combined
    weights and the held state), and a second apply with the same K
    builds no program."""
    sys_, apps = _deployment()
    tr = async_engine.AsyncTrainer(
        sys_, apps[:1], compression=CompressionPolicy(kind="qsgd-int8", downlink="delta-qsgd")
    )
    workers = tr.workers(0)[:4]

    def one_apply():
        for w in workers:
            tr.begin_download(0, w)
        for w in workers:
            tr.commit(0, w, 0.0)
        return tr.apply(0, 0.0)

    one_apply()  # builds every program of the path
    builds = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            builds.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    tracer.enable()
    before = tracer.snapshot()
    try:
        assert one_apply()["arrivals"] == len(workers)
    finally:
        tracer.disable()
        jax.monitoring.unregister_event_duration_listener(on_duration)
    after = tracer.snapshot()
    assert builds == []
    grew = {k: after[k] - before[k] for k in after}
    assert grew["apply_fused"] == 1 and grew["apply_eager"] == 0
    assert grew["broadcast_fused"] == 1 and grew["broadcast_eager"] == 0
    recs = tracer.records

    def under(i, names):
        while i >= 0:
            if recs[i][0] in names:
                return True
            i = recs[i][3]
        return False

    step = ("verb.apply", "broadcast", "chain")
    pulls = [i for i, r in enumerate(recs) if r[0] == "xfer.d2h" and under(i, step)]
    assert 1 <= len(pulls) <= 2


def test_commit_payloads_stay_on_the_device_until_the_apply(tracer):
    """One warm apply of 4 qsgd commits with a delta-qsgd broadcast: no
    quantize of the commit loop pulls, ``ApplyBuffered`` takes all 4
    grids from the device, the apply pulls at most twice outside
    ``train`` (the combined weights and the held state), and no program
    is built."""
    sys_, apps = _deployment()
    tr = async_engine.AsyncTrainer(
        sys_, apps[:1], compression=CompressionPolicy(kind="qsgd-int8", downlink="delta-qsgd")
    )
    workers = tr.workers(0)[:4]

    def one_apply():
        for w in workers:
            tr.begin_download(0, w)
        for w in workers:
            tr.commit(0, w, 0.0)
        return tr.apply(0, 0.0)

    one_apply()  # builds every program of the path
    builds = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            builds.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    tracer.enable()
    before = tracer.snapshot()
    try:
        assert one_apply()["arrivals"] == len(workers)
    finally:
        tracer.disable()
        jax.monitoring.unregister_event_duration_listener(on_duration)
    after = tracer.snapshot()
    assert builds == []
    grew = {k: after[k] - before[k] for k in after}
    assert grew["grid_resident"] == len(workers) and grew["grid_uploaded"] == 0
    recs = tracer.records

    def under(i, names):
        while i >= 0:
            if recs[i][0] in names:
                return True
            i = recs[i][3]
        return False

    commits = [i for i, r in enumerate(recs) if r[0] == "verb.commit"]
    assert len(commits) == len(workers)
    quantizes = [i for i, r in enumerate(recs)
                 if r[0] == "quantize" and not under(i, ("broadcast",))]
    assert len(quantizes) == len(workers) and max(quantizes) < commits[-1]
    assert not [i for i, r in enumerate(recs) if r[0] == "xfer.d2h" and under(i, ("quantize",))]
    outside = [i for i, r in enumerate(recs) if r[0] == "xfer.d2h" and not under(i, ("train",))]
    assert 1 <= len(outside) <= 2
