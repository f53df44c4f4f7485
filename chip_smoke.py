"""Run the system's main paths once on one TPU chip and check what comes out.

    python chip_smoke.py

One process, three phases, run in order; any failed check raises and
the script exits non-zero.  It refuses to start unless JAX's first
device is a TPU and the Pallas kernels are on (``REPRO_KERNEL_MODE`` not
``jnp``): nothing here falls back to the CPU, to interpret mode or to
the jnp kernels.

- **A: async FL main path.**  The canned fixture
  (``tools/profile_sim.canned_fixture``: 600 nodes, 4 zones, 16 apps x 8
  workers, heterogeneous compute, >= 10% churn, 3 applies per app)
  through ``run_async`` with qsgd-int8 commits and delta-qsgd
  broadcasts, then one synchronous ``rounds.run_round``, whose
  hierarchical aggregate is checked against the host float64 path.
  Checks the apply count, churn, finite and falling losses, and that
  every data-plane wrapper compiles to a Mosaic kernel
  (``tpu_custom_call``) on this device.
- **B: kernels at the paper's payload width.**  A ResNet-34-sized delta
  (85,248 rows x 256 = 21.8 M f32) through the same ``kernels.ops``
  wrappers the apply path calls: quantize K=8 commits, aggregate them,
  and apply a ``chain_cap``-deep broadcast chain, each against a host
  float64 reference.
- **C: LM trainer at published widths.**  ``repro.launch.train.run`` on
  tinyllama-1.1b (d_model 2048, 32 heads / 4 KV heads of 64, d_ff 5632,
  vocab 32000), seq 128, global batch 8, 5 AdamW steps, with depth cut
  to what the chip's memory holds.

Each phase prints its set-up time (fixture, inputs and compiles), its
run time, its XLA program count and its checks, and the device's
``peak_bytes_in_use`` so far.  The last line of standard output is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import gc
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(REPO, "src"), REPO]

# phase A: the canned async fixture
M_APPS, WORKERS, APPLIES, SEED = 16, 8, 3, 0
# phase B: a ResNet-34-sized delta on the (rows, 256) quantization grid
PAYLOAD_ROWS, COMMITS = 85_248, 8
# phase C: tinyllama-1.1b at published widths
LM_ARCH, LM_SEQ, LM_BATCH, LM_STEPS = "tinyllama-1.1b", 128, 8, 5
# AdamW bytes per parameter: bf16 param + f32 master, m and v + f32 grad
LM_BYTES_PER_PARAM = 2 + 3 * 4 + 4
LM_MEMORY_SHARE = 0.9  # of the device's bytes_limit; the rest is headroom

AGG_RTOL = 1e-5
CHAIN_RTOL = 1e-6


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    print(f"  check ok: {what}")


def rel_err(got, ref) -> float:
    """max |got - ref| / max |ref| (float64)."""
    import numpy as np

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / max(float(np.max(np.abs(ref))), 1e-30))


class CompileLog:
    """XLA programs built in this process, read from JAX's monitoring
    events: each program built (compiled, or loaded from the persistent
    cache) with its name and seconds, and the persistent-cache hits."""

    _BUILD = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.builds: list[tuple[float, str]] = []
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, fun_name="", **_):
        if event == self._BUILD:
            self.builds.append((secs, fun_name))

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.hits += 1

    def mark(self) -> tuple[int, int]:
        return len(self.builds), self.hits

    def since(self, mark) -> tuple[list[tuple[float, str]], int]:
        return self.builds[mark[0]:], self.hits - mark[1]


def report(name, device, setup_s, run_s, compiles) -> None:
    builds, hits = compiles
    secs = sum(s for s, _ in builds)
    slowest = ", ".join(f"{n} {s:.3f} s" for s, n in sorted(builds, reverse=True)[:3])
    peak = device.memory_stats()["peak_bytes_in_use"]
    print(f"phase {name}: set-up {setup_s:.3f} s, run {run_s:.3f} s; "
          f"XLA programs {len(builds)} ({hits} from the persistent cache, "
          f"{len(builds) - hits} compiled, {secs:.3f} s building them; "
          f"slowest: {slowest}); "
          f"peak_bytes_in_use {peak} ({peak / 2**30:.3f} GiB)", flush=True)


def assert_mosaic(name, fn, *args) -> None:
    """``fn`` (a ``kernels.ops`` wrapper) compiles to a Mosaic kernel on
    this device — neither interpret mode nor the jnp fallback."""
    import jax

    text = jax.jit(fn).lower(*args).compile().as_text()
    check("tpu_custom_call" in text, f"{name} compiles to tpu_custom_call")


# -- phase A ------------------------------------------------------------------


def phase_a(device, log) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fl import async_engine, engine, rounds
    from repro.fl import small_models as sm
    from repro.fl.compression import CompressionPolicy
    from repro.kernels import ops
    from tools.profile_sim import canned_fixture

    def app_loss(app) -> float:
        """The app's global model on all of its workers' data: the
        federated objective its applies should lower."""
        ws = sorted(app.data)
        x = jnp.asarray(np.concatenate([app.data[w][0] for w in ws]))
        y = jnp.asarray(np.concatenate([app.data[w][1] for w in ws]))
        logp = jax.nn.log_softmax(sm.LOGITS[app.model](app.params, x))
        return float(-jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1)))

    print(f"phase A: async FL main path, M={M_APPS} apps x {WORKERS} workers, "
          f"{APPLIES} applies each, qsgd-int8 commits + delta-qsgd broadcasts", flush=True)
    mark = log.mark()
    t_phase = time.perf_counter()
    engine.DISPATCH.reset()
    policy = CompressionPolicy(kind="qsgd-int8", downlink="delta-qsgd")
    system, apps, run_kwargs = canned_fixture(m_apps=M_APPS, workers=WORKERS, seed=SEED)
    before = [app_loss(app) for app in apps]
    t0 = time.perf_counter()
    res = async_engine.run_async(system, apps, applies=APPLIES, compression=policy,
                                 **run_kwargs)
    async_s = time.perf_counter() - t0
    print(f"  async run: {async_s:.3f} s wall; {len(res['events'])} applies, "
          f"{len(res['churn'])} churn events; engine dispatches "
          f"{engine.DISPATCH.dispatches}, engine compiles {engine.DISPATCH.compiles}")
    check(len(res["events"]) == M_APPS * APPLIES,
          f"applies == M x applies ({len(res['events'])} == {M_APPS * APPLIES})")
    check(len(res["churn"]) > 0, f"churn happened ({len(res['churn'])} events)")
    local = [r["loss"] for r in res["history"]]
    check(len(local) == M_APPS * APPLIES and all(map(math.isfinite, local)),
          f"{len(local)} per-apply local losses finite")
    for app, first in zip(apps, before):
        last = app_loss(app)
        check(math.isfinite(last) and last < first,
              f"{app.name}: global loss falls {first:.4f} -> {last:.4f}")

    # one synchronous round: the hierarchical Aggregate through
    # tree_aggregate_groups, compared with the host float64 path
    app = apps[0]
    seen = {}
    aggregate = system.Aggregate

    def recording_aggregate(app_id, objects, weights=None, **kw):
        out = aggregate(app_id, objects, weights, **kw)
        seen.update(objects=objects, weights=weights, result=out["result"])
        return out

    system.Aggregate = recording_aggregate  # records the round's payload
    try:
        t1 = time.perf_counter()
        m = rounds.run_round(system, app)
        sync_s = time.perf_counter() - t1
    finally:
        del system.Aggregate
    ref = system.Aggregate(app.handle.app_id, seen["objects"], seen["weights"],
                           use_kernel=False)["result"]
    flat = lambda t: np.concatenate([np.ravel(np.asarray(l)) for l in jax.tree.leaves(t)])
    err = rel_err(flat(seen["result"]), flat(ref))
    print(f"  sync round: {sync_s:.3f} s wall, {len(m['agg_levels'])} tree levels, "
          f"{len(seen['objects'])} workers; aggregate vs float64 host path: "
          f"max rel err {err:.3e}")
    check(math.isfinite(m["loss"]), f"sync round loss finite ({m['loss']:.4f})")
    check(err <= AGG_RTOL, f"sync aggregate within {AGG_RTOL:g} of float64 ({err:.3e})")

    # every data-plane wrapper on the apply path compiles to Mosaic here
    n = sum(int(np.size(l)) for l in jax.tree.leaves(app.params))
    rows = -(-n // 256)
    x = jnp.zeros((rows, 256), jnp.float32)
    q = jnp.zeros((rows, 256), jnp.int8)
    s = jnp.ones((rows, 1), jnp.float32)
    assert_mosaic("qsgd_quantize", lambda x, r: ops.qsgd_quantize(x, r), x, x)
    assert_mosaic("qsgd_dequantize", ops.qsgd_dequantize, q, s)
    assert_mosaic(
        "buffered_aggregate_quantized",
        lambda q, s: ops.buffered_aggregate_quantized(
            [q] * 4, [s] * 4, [1.0] * 4, [0, 1, 2, 3])[0], q, s)
    assert_mosaic("apply_quantized_broadcast", ops.apply_quantized_broadcast,
                  x, jnp.stack([q] * policy.chain_cap), jnp.stack([s] * policy.chain_cap))
    assert_mosaic("tree_aggregate_groups", ops.tree_aggregate_groups,
                  jnp.zeros((3, 4, n), jnp.float32), jnp.ones((3, 4), jnp.float32))
    # the phase compiles inside its run: its set-up is the XLA build time
    compiles = log.since(mark)
    build_s = sum(s for s, _ in compiles[0])
    report("A", device, build_s, time.perf_counter() - t_phase - build_s, compiles)


# -- phase B ------------------------------------------------------------------


def phase_b(device, log) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fl.compression import CompressionPolicy
    from repro.kernels import ops

    R, K = PAYLOAD_ROWS, COMMITS
    D = CompressionPolicy().chain_cap
    levels = 127
    print(f"phase B: kernels at payload width, {R} x 256 = {R * 256} f32 per delta, "
          f"K={K} commits, chain depth {D}", flush=True)
    mark = log.mark()
    t0 = time.perf_counter()
    key = jax.random.key(SEED)
    x = 0.01 * jax.random.normal(jax.random.fold_in(key, 0), (K, R, 256), jnp.float32)
    u = jax.random.uniform(jax.random.fold_in(key, 1), (K, R, 256), jnp.float32)
    w0 = 0.05 * jax.random.normal(jax.random.fold_in(key, 2), (R, 256), jnp.float32)
    rng = np.random.default_rng(SEED)
    weights = rng.uniform(0.5, 3.0, K).tolist()
    stale = rng.integers(0, 5, K).tolist()

    def run_kernels():
        qs, ss = zip(*(ops.qsgd_quantize(x[k], u[k], levels=levels) for k in range(K)))
        deq = ops.qsgd_dequantize(qs[0], ss[0])
        agg, _ = ops.buffered_aggregate_quantized(qs, ss, weights, stale, alpha=0.5)
        chain = ops.apply_quantized_broadcast(w0, jnp.stack(qs[:D]), jnp.stack(ss[:D]))
        return jax.block_until_ready((qs, ss, deq, agg, chain))

    run_kernels()  # compiles every shape
    setup_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    qs, ss, deq, agg, chain = run_kernels()
    run_s = time.perf_counter() - t1

    # host float64 references, one commit at a time
    disc = np.asarray(weights) * (1.0 + np.asarray(stale, np.float64)) ** -0.5
    agg_ref = np.zeros((R, 256))
    chain_ref = np.asarray(w0, np.float64)
    lattice, off, scale_err = 0.0, 0, 0.0
    for k in range(K):
        xk = np.asarray(x[k], np.float64)
        s_ref = np.maximum(np.max(np.abs(xk), axis=-1, keepdims=True) / levels, 1e-12)
        q_ref = np.floor(xk / s_ref + np.asarray(u[k], np.float64))
        qk, sk = np.asarray(qs[k], np.float64), np.asarray(ss[k], np.float64)
        lattice = max(lattice, float(np.max(np.abs(qk - q_ref))))
        off += int(np.count_nonzero(qk != q_ref))
        scale_err = max(scale_err, rel_err(sk, s_ref))
        if k == 0:
            deq_step = float(np.max(np.abs(np.asarray(deq, np.float64) - xk) / s_ref))
            deq_err = rel_err(deq, qk * sk)
        agg_ref += disc[k] * (qk * sk)
        if k < D:
            chain_ref = chain_ref + qk * sk
    agg_err = rel_err(agg, agg_ref.reshape(-1) / disc.sum())
    chain_err = rel_err(chain, chain_ref)
    print(f"  largest errors: qsgd_quantize lattice {lattice:g} step(s) "
          f"({off} of {K * R * 256} points off), scale rel {scale_err:.3e}; "
          f"qsgd_dequantize rel {deq_err:.3e}, |deq - x| {deq_step:.6f} step(s); "
          f"buffered_aggregate_quantized rel {agg_err:.3e}; "
          f"apply_quantized_broadcast rel {chain_err:.3e}")
    check(lattice <= 1.0, f"quantized lattice within one step of float64 ({lattice:g})")
    check(scale_err <= 1e-6, f"quantizer scales match float64 ({scale_err:.3e})")
    check(deq_err <= 1e-6, f"dequantize == q * scale ({deq_err:.3e})")
    check(deq_step <= 1.0 + 1e-5, f"dequantized values within one step ({deq_step:.6f})")
    check(agg_err <= AGG_RTOL, f"aggregate within {AGG_RTOL:g} of float64 ({agg_err:.3e})")
    check(chain_err <= CHAIN_RTOL, f"chain apply within {CHAIN_RTOL:g} of float64 ({chain_err:.3e})")
    report("B", device, setup_s, run_s, log.since(mark))


# -- phase C ------------------------------------------------------------------


def lm_depth(cfg, bytes_limit: int) -> tuple[int, int]:
    """The deepest cut of ``cfg`` whose AdamW training state and f32
    gradients fit ``LM_MEMORY_SHARE`` of the device; returns (layers,
    params at that depth).  Widths are never changed."""
    import jax

    from repro.models import lm

    def count(layers):
        shapes = jax.eval_shape(
            lambda k: lm.init_params(k, cfg.replace(num_layers=layers)), jax.random.key(0))
        return sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))

    base, per_layer = count(1), count(2) - count(1)
    base -= per_layer
    budget = LM_MEMORY_SHARE * bytes_limit / LM_BYTES_PER_PARAM
    layers = min(cfg.num_layers, int((budget - base) // per_layer))
    check(layers >= 1, f"at least one {cfg.name} layer fits ({layers})")
    return layers, base + layers * per_layer


def phase_c(device, log) -> None:
    from repro import configs
    from repro.launch import train

    cfg = configs.get_config(LM_ARCH)
    limit = device.memory_stats()["bytes_limit"]
    layers, params = lm_depth(cfg, limit)
    print(f"phase C: {cfg.name} at published widths (d_model {cfg.d_model}, "
          f"{cfg.num_heads} heads / {cfg.num_kv_heads} KV heads x {cfg.head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); depth cut {cfg.num_layers} -> "
          f"{layers} layers ({params / 1e9:.3f} B params x {LM_BYTES_PER_PARAM} B = "
          f"{params * LM_BYTES_PER_PARAM / 2**30:.2f} GiB of {limit / 2**30:.2f} GiB "
          f"bytes_limit); seq {LM_SEQ}, global batch {LM_BATCH}, {LM_STEPS} steps", flush=True)
    mark = log.mark()
    out = train.run([
        "--arch", LM_ARCH, "--layers", str(layers), "--steps", str(LM_STEPS),
        "--seq-len", str(LM_SEQ), "--global-batch", str(LM_BATCH), "--log-every", "1",
    ])
    losses = out["losses"]
    check(out["cfg"].d_model == cfg.d_model and out["cfg"].num_layers == layers,
          "trained config is the published widths at the cut depth")
    check(len(losses) == LM_STEPS and all(map(math.isfinite, losses)),
          f"{LM_STEPS} finite losses {[round(l, 4) for l in losses]}")
    check(losses[-1] < losses[0], f"loss falls ({losses[0]:.4f} -> {losses[-1]:.4f})")
    report("C", device, out["setup_s"], out["run_s"], log.since(mark))


def main() -> None:
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: jax.devices()[0].platform is {d0.platform!r}; "
            "this check runs only on a TPU chip")

    from repro.kernels import ops
    from repro.launch import compile_cache

    if ops.kernel_mode() == "jnp":
        raise SystemExit("chip_smoke: REPRO_KERNEL_MODE=jnp turns the Pallas kernels off; unset it")
    cache_dir = compile_cache.configure()
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}; kernel mode {ops.kernel_mode()}")
    print(f"compile cache: {cache_dir}", flush=True)
    log = CompileLog()
    t0 = time.perf_counter()
    for phase in (phase_a, phase_b, phase_c):
        phase(d0, log)
        gc.collect()
    print(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
