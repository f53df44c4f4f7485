"""Compressed downlink: version-cached broadcast of quantized deltas.

Locks down the broadcast direction end to end:

- **Policy surface**: the ``downlink``/``downlink_levels``/``chain_cap``
  axis validates its domain; ``downlink_bits`` is the minimal fixed
  width for the coarse lattice; ``downlink_wire_bytes`` prices a chain
  of k packed deltas, the quantized full model, or the f32 fallback.
- **Fused kernel parity**: ``ops.apply_quantized_broadcast`` agrees
  with the eager ``ref.apply_quantized_ref`` in BOTH kernel modes
  (pallas-interpret and compiled jnp), including row counts that need
  block padding, and the two modes agree bit for bit.
- **Reference reconstruction**: chained cached deltas from any base
  land bit-for-bit on the master's incrementally-maintained reference
  state (chain 1 and chain ``chain_cap``), the reference stays within
  one quantizer step of the true params (downlink error feedback never
  compounds), and a base past the cache window raises ``KeyError`` —
  the gap the scheduler prices as a full f32 fallback.
- **Scheduler pricing**: ``_download_mbit`` charges chain * packed
  delta bytes inside the window, the full f32 state with no cached
  base or past ``chain_cap``, and zero bytes for a version check
  (chain 0); a churned worker loses its base and rejoins on the full
  path.  The downlink ledger splits from the uplink ledger in
  ``transport_stats`` and the fairness log.
- **Trace identity**: ``downlink="none"`` is provably free — downlink
  knobs are inert and the M=16 churn trace is byte-identical to the
  plain uplink-only policy, in exact and sampled pricing.
- **EF-SGD**: with deterministic rounding a plain quantizer's commit
  stream carries a persistent bias; error feedback drives the running
  mean of dequantized commits to the true value.
- **signSGD / top-k**: closed-form ``wire_bytes`` equals the real
  ``QuantizedDelta.nbytes`` and is what the scheduler prices commits
  at; trained runs converge finitely.
"""
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import TotoroSystem
from repro.core.sim import AsyncBufferScheduler, ChurnModel
from repro.fl import compression as comp
from repro.fl.compression import CompressionPolicy
from repro.kernels import ops as kops
from repro.kernels import ref


@pytest.fixture
def kernel_mode_guard():
    prev = kops.kernel_mode()
    yield
    kops.set_kernel_mode(prev)


# -- policy surface ------------------------------------------------------------


def test_downlink_policy_validation():
    with pytest.raises(ValueError):
        CompressionPolicy(downlink="zip")
    with pytest.raises(ValueError):
        CompressionPolicy(downlink="delta-qsgd", downlink_levels=0)
    with pytest.raises(ValueError):
        CompressionPolicy(downlink="delta-qsgd", downlink_levels=128)
    with pytest.raises(ValueError):
        CompressionPolicy(downlink="delta-qsgd", chain_cap=0)
    with pytest.raises(ValueError):
        CompressionPolicy(kind="topk", topk_frac=0.0)
    assert not CompressionPolicy().downlink_enabled
    assert CompressionPolicy(downlink="delta-qsgd").downlink_enabled


@pytest.mark.parametrize("levels,bits", [(1, 2), (3, 3), (7, 4), (15, 5), (127, 8)])
def test_downlink_bits_minimal_width(levels, bits):
    # 2*levels+1 lattice points need ceil(log2(2L+1)) bits
    assert CompressionPolicy(downlink="delta-qsgd", downlink_levels=levels).downlink_bits == bits


def test_downlink_wire_bytes_model():
    p = CompressionPolicy(kind="qsgd-int8", downlink="delta-qsgd", downlink_levels=7)
    payload = 2_000_000.0
    rows = math.ceil(payload / 4.0 / p.chunk)
    one = rows * math.ceil(p.chunk * 4 / 8) + rows * 4
    assert p.delta_wire_bytes(payload) == float(one)
    assert p.downlink_wire_bytes(payload, chain=0) == 0.0
    assert p.downlink_wire_bytes(payload, chain=1) == float(one)
    assert p.downlink_wire_bytes(payload, chain=3) == float(3 * one)
    assert p.downlink_wire_bytes(payload, chain=None) == payload  # f32 fallback
    with pytest.raises(ValueError):
        p.downlink_wire_bytes(payload, chain=-1)
    # a 4-bit packed delta is ~1/8 of the f32 state, far under the int8 floor
    assert p.delta_wire_bytes(payload) < 0.14 * payload
    q8 = CompressionPolicy(kind="qsgd-int8", downlink="qsgd-int8")
    assert q8.downlink_wire_bytes(payload) == float(rows * q8.chunk + rows * 4)
    assert q8.downlink_wire_bytes(payload, chain=2) == q8.downlink_wire_bytes(payload)
    off = CompressionPolicy(kind="qsgd-int8")
    assert off.downlink_wire_bytes(payload) == payload


def test_broadcast_key_decorrelated_from_commit_key():
    p = CompressionPolicy(kind="qsgd-int8", downlink="delta-qsgd")
    for app, v in [(0, 0), (1, 3), (2, 7)]:
        bk = np.asarray(comp.broadcast_key(p, app, v))
        ck = np.asarray(comp.commit_key(p, app, v))
        assert not np.array_equal(bk, ck)


# -- fused dequantize-and-apply kernel -----------------------------------------


def _chain_case(seed, rows, depth):
    rng = np.random.default_rng(seed)
    w = rng.normal(0, 1.0, (rows, 256)).astype(np.float32)
    q = rng.integers(-7, 8, (depth, rows, 256)).astype(np.int8)
    s = rng.uniform(1e-4, 1e-2, (depth, rows, 1)).astype(np.float32)
    return w, q, s


@pytest.mark.parametrize("rows,depth", [(4, 1), (4, 3), (3, 2), (300, 3)])
def test_apply_quantized_parity_both_modes(kernel_mode_guard, rows, depth):
    w, q, s = _chain_case(rows, rows, depth)
    want = np.asarray(ref.apply_quantized_ref(w, q, s))
    got = {}
    for mode in ("pallas", "jnp"):
        kops.set_kernel_mode(mode)
        got[mode] = np.asarray(kops.apply_quantized_broadcast(w, q, s))
        assert got[mode].shape == w.shape
        # jit fuses the multiply-add (FMA) so eager-ref agreement is fp-tight
        np.testing.assert_allclose(got[mode], want, rtol=0, atol=1e-5)
    np.testing.assert_array_equal(got["pallas"], got["jnp"])


def test_apply_quantized_chain_order(kernel_mode_guard):
    """One D-deep call == D successive single-delta calls, per mode."""
    w, q, s = _chain_case(7, 8, 3)
    for mode in ("pallas", "jnp"):
        kops.set_kernel_mode(mode)
        fused = np.asarray(kops.apply_quantized_broadcast(w, q, s))
        step = w
        for d in range(3):
            step = np.asarray(kops.apply_quantized_broadcast(step, q[d : d + 1], s[d : d + 1]))
        np.testing.assert_array_equal(fused, step)


# -- reference reconstruction --------------------------------------------------


def _master_walk(pol, versions=5, seed=0):
    """Simulate the master's broadcast-state maintenance: returns the
    per-version reference states, the delta cache, and the true params."""
    rng = np.random.default_rng(seed)
    params = {"w": rng.normal(0, 1, (40, 13)).astype(np.float32),
              "b": rng.normal(0, 1, (17,)).astype(np.float32)}
    recon, cache, states = params, {}, {0: params}
    true, trues = params, {0: params}
    for v in range(1, versions + 1):
        true = jax.tree.map(
            lambda p: p + rng.normal(0, 0.01, p.shape).astype(np.float32), true
        )
        trues[v] = true
        delta = jax.tree.map(lambda a, b: a - b, true, recon)
        qd = comp.quantize_broadcast_delta(delta, pol, comp.broadcast_key(pol, 0, v))
        cache[v] = qd
        recon = comp.apply_delta_chain(recon, [qd])
        states[v] = recon
    return states, cache, trues


def test_chained_reconstruction_bit_exact_at_1_and_cap():
    pol = CompressionPolicy(kind="qsgd-int8", downlink="delta-qsgd", chain_cap=3)
    states, cache, trues = _master_walk(pol)
    for base in (4, 2):  # chain lengths 1 and chain_cap
        chain = [cache[v] for v in range(base + 1, 6)]
        got = comp.apply_delta_chain(states[base], chain)
        for k in ("w", "b"):
            np.testing.assert_array_equal(got[k], states[5][k])
    # downlink error feedback: the reference's drift from the TRUE params
    # is bounded by one quantizer step at EVERY version — quantizing each
    # delta against the reference absorbs the error, it never compounds
    for v in range(1, 6):
        step = float(cache[v].scale.max())
        for k in ("w", "b"):
            drift = np.abs(states[v][k] - trues[v][k]).max()
            assert drift <= step + 1e-6, (v, k, drift, step)


@pytest.mark.parametrize("dtype,chunk", [(np.float32, 256), (jnp.bfloat16, 256),
                                         (np.float32, 128)])
def test_device_broadcast_state_matches_host_path(dtype, chunk):
    """``AsyncTrainer._broadcast_state`` keeps the delta-qsgd reference
    on the device: over 5 versions its held state and its cached q and
    scale equal the host path (``quantize_broadcast_delta`` on
    ``params - reference``, then ``apply_delta_chain``) bit for bit, in
    float32 and in bfloat16 (the grid rebuilt from the rounded state),
    at the kernels' chunk and another, and a stale base chaining its gap
    from the cache lands on the same state."""
    from repro import tracing
    from repro.fl import async_engine

    pol = CompressionPolicy(kind="qsgd-int8", downlink="delta-qsgd", chain_cap=3, chunk=chunk)
    rng = np.random.default_rng(1)
    params = {"w": jnp.asarray(rng.normal(0, 1, (40, 13)), dtype),
              "b": jnp.asarray(rng.normal(0, 1, (17,)), dtype)}
    app = types.SimpleNamespace(params=params, handle=None)
    tr = async_engine.AsyncTrainer(None, [app], compression=pol)
    recon, states = params, {0: params}
    before = tracing.snapshot()
    tracing.enable()
    try:
        for v in range(1, 6):
            params = jax.tree.map(
                lambda p: (p + jnp.asarray(rng.normal(0, 0.01, p.shape), p.dtype)).astype(dtype),
                params,
            )
            held = tr._broadcast_state(0, params, v, pol)
            delta = jax.tree.map(
                lambda p, r: np.asarray(p, np.float32) - np.asarray(r, np.float32), params, recon
            )
            qd = comp.quantize_broadcast_delta(delta, pol, app=0, version=v)
            recon = states[v] = comp.apply_delta_chain(recon, [qd])
            cached = tr._delta_cache[0][v]
            np.testing.assert_array_equal(np.asarray(cached.q), qd.q)
            np.testing.assert_array_equal(np.asarray(cached.scale), qd.scale)
            assert cached.nbytes == qd.nbytes
            for k in ("w", "b"):
                assert isinstance(held[k], np.ndarray) and held[k].dtype == dtype
                np.testing.assert_array_equal(held[k], recon[k])
    finally:
        tracing.disable()
        tracing.clear()
    after = tracing.snapshot()
    assert after["broadcast_fused"] - before["broadcast_fused"] == 5
    assert after["broadcast_eager"] == before["broadcast_eager"]
    # chain lengths 1 and chain_cap, from the device cache; a bfloat16
    # state is rounded at every version, so only its chain of one lands
    # on it bit for bit
    for base in (4, 2) if dtype == np.float32 else (4,):
        got = comp.apply_delta_chain(states[base], tr.delta_chain(0, base, 5))
        for k in ("w", "b"):
            np.testing.assert_array_equal(got[k], states[5][k])


def test_apply_delta_chain_rejects_mismatched_grid():
    pol = CompressionPolicy(kind="qsgd-int8", downlink="delta-qsgd")
    _, cache, _ = _master_walk(pol, versions=1)
    wrong = {"w": np.zeros((3, 3), np.float32)}
    with pytest.raises(ValueError):
        comp.apply_delta_chain(wrong, [cache[1]])
    assert comp.apply_delta_chain(wrong, []) is wrong  # empty chain: no-op


# -- scheduler pricing ---------------------------------------------------------


def _build_handles(m, workers=4, n_nodes=160, seed=0):
    sys_ = TotoroSystem(zone_bits=2, suffix_bits=22, seed=seed)
    rng = np.random.default_rng(seed)
    nodes = [
        sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2),
                  bandwidth=float(rng.uniform(20, 100)))
        for i in range(n_nodes)
    ]
    handles = []
    for a in range(m):
        h = sys_.CreateTree(f"dl-{m}-{a}")
        for w in rng.choice(nodes, size=workers, replace=False):
            sys_.Subscribe(h.app_id, int(w))
        handles.append(h)
    return sys_, handles


def _trace(m, *, compression, seed=0, applies=2, churn=True,
           model_bytes=2e5, **sched_kw):
    sys_, handles = _build_handles(m, seed=seed)
    sched = AsyncBufferScheduler(
        sys_, handles, model_bytes=model_bytes, compute_ms=25.0, buffer_k=3,
        churn=ChurnModel(period_ms=400.0, downtime_ms=600.0, group_size=2, seed=9)
        if churn else None,
        app_compression=compression, **sched_kw,
    )
    events = sched.run(applies, max_events=500_000)
    return events, list(sched.churn_log), list(sched.fairness_log), sched


DELTA = CompressionPolicy(kind="qsgd-int8", downlink="delta-qsgd")


def test_download_mbit_chain_selection():
    """The pricing decision table, hit directly: no base -> full, gap in
    [0, cap] -> chain, gap > cap -> full fallback."""
    sys_, handles = _build_handles(1)
    sched = AsyncBufferScheduler(
        sys_, handles, model_bytes=2e5, app_compression=DELTA
    )
    sched._version = [7]
    senders = np.asarray([0, 1], np.int64)
    w = next(iter(handles[0].tree.members))
    full = float(sched.model_bytes)
    one = DELTA.delta_wire_bytes(sched.model_bytes)

    def price(base):
        sched._worker_base.pop((0, w), None)
        if base is not None:
            sched._worker_base[(0, w)] = base
        mbit = sched._download_mbit(0, w, senders)
        t, ai, ww, chain, nbytes = sched.downlink_log[-1]
        assert (ai, ww) == (0, w)
        assert sched._worker_base[(0, w)] == 7  # base advanced to current
        assert sched._pending_down_bytes[(0, w)] == nbytes * len(senders)
        assert mbit == nbytes * 8e-6
        return chain, nbytes

    assert price(None) == (None, full)        # first download: no base
    assert price(7) == (0, 0.0)               # version check, zero payload
    assert price(6) == (1, one)
    assert price(7 - DELTA.chain_cap) == (DELTA.chain_cap, DELTA.chain_cap * one)
    assert price(7 - DELTA.chain_cap - 1) == (None, full)  # over cap: fallback


def test_downlink_log_and_ledger_delta_run():
    events, _, fair, sched = _trace(4, compression=DELTA, churn=False)
    assert events
    cap = DELTA.chain_cap
    one = DELTA.delta_wire_bytes(sched.model_bytes)
    full = float(sched.model_bytes)
    first_seen = set()
    for _, ai, w, chain, nbytes in sched.downlink_log:
        if (ai, w) not in first_seen:
            first_seen.add((ai, w))
            assert chain is None and nbytes == full  # cold start: full path
        if chain is None:
            assert nbytes == full
        else:
            assert 0 <= chain <= cap
            assert nbytes == chain * one
    stats = sched.transport_stats()
    assert len(stats["downlink_bytes"]) == 4
    assert all(b > 0 for b in stats["downlink_bytes"])
    # the ledger is exactly the credited per-cycle stashes
    assert "downlink_bytes" in fair[-1]
    # an uncompressed run's downlink ledger prices full-model legs
    _, _, _, base = _trace(4, compression=None, churn=False)
    assert sum(base.transport_stats()["downlink_bytes"]) > sum(stats["downlink_bytes"])


def test_churn_rejoin_worker_downloads_full_state():
    sys_, handles = _build_handles(8, seed=1)
    sched = AsyncBufferScheduler(
        sys_, handles, model_bytes=2e5, compute_ms=25.0, buffer_k=3,
        churn=ChurnModel(period_ms=200.0, downtime_ms=150.0, group_size=2, seed=9),
        app_compression=DELTA,
    )
    sched.run(6, max_events=500_000)
    churn_log = sched.churn_log
    fails = [(r.time_ms, set(r.nodes)) for r in churn_log if r.kind == "fail"]
    assert fails
    checked = 0
    for t_fail, victims in fails:
        for t, ai, w, chain, nbytes in sched.downlink_log:
            if w in victims and t > t_fail:
                # first post-fail download for this (app, worker): the
                # cached base was dropped, so the full path is priced
                assert chain is None and nbytes == float(sched.model_bytes)
                checked += 1
                victims = victims - {w}
    assert checked > 0


def test_delta_cache_window_and_keyerror_past_it():
    from benchmarks.common import build_system
    from repro import data as data_mod
    from repro.fl import async_engine, rounds

    sys_, nodes, rng = build_system(n_nodes=60, zones=3, seed=0)
    x, y = data_mod.synthetic_classification(4 * 24, 16, 4, seed=5)
    parts = data_mod.dirichlet_partition(y, 4, alpha=1.0, seed=6)
    ws = [int(n) for n in rng.choice(nodes, size=4, replace=False)]
    app = rounds.make_app(
        sys_, "dlw", workers=ws,
        data_by_worker={n: (x[parts[i]], y[parts[i]]) for i, n in enumerate(ws)},
        dim=16, num_classes=4, local_steps=1, lr=0.2, seed=0,
    )
    out = async_engine.run_async(
        sys_, [app], applies=6, buffer_k=3, model_bytes=2e5,
        compute_ms=10.0, compression=DELTA,
    )
    tr = out["trainer"]
    cur = tr.version[0]
    cap = DELTA.chain_cap
    assert cur > cap
    cached = sorted(tr._delta_cache[0])
    assert cached == list(range(cur - cap + 1, cur + 1))  # bounded window
    assert len(tr.delta_chain(0, cur - 1, cur)) == 1
    assert len(tr.delta_chain(0, cur - cap, cur)) == cap
    with pytest.raises(KeyError):
        tr.delta_chain(0, cur - cap - 1, cur)  # past the window: full path


# -- downlink="none" trace identity --------------------------------------------


def test_downlink_none_knobs_are_inert_m16_churn():
    """Uplink-only compression with downlink="none" must not read ANY
    downlink knob: varying them produces byte-identical ApplyEvents,
    ChurnRecords and fairness logs at M=16 under churn."""
    up = CompressionPolicy(kind="qsgd-int8")
    up_weird = CompressionPolicy(
        kind="qsgd-int8", downlink="none", downlink_levels=1, chain_cap=9
    )
    base = _trace(16, compression=up)
    off = _trace(16, compression=up_weird)
    assert base[0] == off[0]
    assert base[1] == off[1]
    assert base[2] == off[2]
    assert base[3].downlink_log == [] == off[3].downlink_log


def test_downlink_none_identity_under_legacy_and_sampled_pricing():
    kw = dict(congestion_mode="sampled", churn=False)
    base = _trace(4, compression=CompressionPolicy(kind="qsgd-int8"), **kw)
    off = _trace(
        4,
        compression=CompressionPolicy(kind="qsgd-int8", chain_cap=7),
        **kw,
    )
    assert base[:3] == off[:3]


# -- EF-SGD: error feedback drives the commit-stream bias to zero --------------


def test_error_feedback_unbiases_deterministic_rounding():
    """Deterministic round-half-down quantization repeats the SAME error
    every round on a constant gradient — the running mean of dequantized
    commits keeps a persistent bias.  EF-SGD folds the residual into the
    next commit, so the running mean converges to the true value."""
    pol = CompressionPolicy(kind="qsgd-int8", levels=3)
    rng = np.random.default_rng(3)
    x = {"g": rng.normal(0, 1, (2, 200)).astype(np.float32)}
    T = 64

    plain_sum = np.zeros_like(x["g"])
    for _ in range(T):
        qd = comp.quantize_delta(x, pol)  # key=None: round-half-down
        plain_sum += qd.dequantize()["g"]
    plain_bias = np.abs(plain_sum / T - x["g"]).mean()

    ef_sum = np.zeros_like(x["g"])
    resid = {"g": np.zeros_like(x["g"])}
    for _ in range(T):
        target = {"g": x["g"] + resid["g"]}
        qd = comp.quantize_delta(target, pol)
        deq = qd.dequantize()["g"]
        resid = {"g": target["g"] - deq}
        ef_sum += deq
    ef_bias = np.abs(ef_sum / T - x["g"]).mean()

    assert plain_bias > 1e-3          # the coarse lattice really does drift
    assert ef_bias < 0.1 * plain_bias  # EF drives the mean onto the target


# -- signSGD / top-k: first-class kinds priced through the commit path ---------


@pytest.mark.parametrize("pol", [
    CompressionPolicy(kind="signsgd"),
    CompressionPolicy(kind="topk", topk_frac=0.02),
])
def test_wire_model_matches_real_delta_nbytes(pol):
    rng = np.random.default_rng(0)
    delta = {"a": rng.normal(0, 1, (37, 19)).astype(np.float32),
             "b": rng.normal(0, 1, (111,)).astype(np.float32)}
    n = sum(v.size for v in delta.values())
    qd = comp.quantize_delta(delta, pol, comp.commit_key(pol, 0, 0))
    assert qd.nbytes == pol.wire_bytes(4.0 * n)
    # the scheduler prices commits at exactly this closed form
    sys_, handles = _build_handles(2)
    sched = AsyncBufferScheduler(
        sys_, handles, model_bytes=4.0 * n, app_compression=pol
    )
    assert sched._commit_bytes[0] == pol.wire_bytes(4.0 * n)
    assert qd.nbytes < 0.3 * 4.0 * n  # both kinds beat dense int8


def test_signsgd_scale_ignores_padding():
    pol = CompressionPolicy(kind="signsgd", chunk=8)
    delta = {"a": np.asarray([1.0, -1.0, 1.0], np.float32)}  # 3 of 8 slots
    qd = comp.quantize_delta(delta, pol)
    # mean |x| over the REAL 3 elements, not the 8-slot padded row
    assert qd.scale[0, 0] == pytest.approx(1.0)
    np.testing.assert_array_equal(
        qd.dequantize()["a"], np.asarray([1.0, -1.0, 1.0], np.float32)
    )


def test_trained_signsgd_and_topk_converge_finite():
    from benchmarks.common import build_system
    from repro import data as data_mod
    from repro.fl import async_engine, rounds

    def train(pol):
        sys_, nodes, rng = build_system(n_nodes=60, zones=3, seed=0)
        x, y = data_mod.synthetic_classification(4 * 24, 16, 4, seed=7)
        parts = data_mod.dirichlet_partition(y, 4, alpha=1.0, seed=8)
        ws = [int(n) for n in rng.choice(nodes, size=4, replace=False)]
        app = rounds.make_app(
            sys_, "sk", workers=ws,
            data_by_worker={n: (x[parts[i]], y[parts[i]]) for i, n in enumerate(ws)},
            dim=16, num_classes=4, local_steps=2, lr=0.2, seed=0,
        )
        return async_engine.run_async(
            sys_, [app], applies=4, buffer_k=3, model_bytes=2e5,
            compute_ms=10.0, compression=pol,
        )

    for kind in ("signsgd", "topk"):
        out = train(CompressionPolicy(kind=kind, topk_frac=0.05))
        losses = [r["loss"] for r in out["history"]]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0] * 2.0  # no blow-up on the tiny fixture

    ef = train(CompressionPolicy(kind="qsgd-int8", levels=7, error_feedback=True))
    assert all(np.isfinite([r["loss"] for r in ef["history"]]))
    assert any(len(d) for d in ef["trainer"]._ef)  # residuals really carried
