"""Async buffered FL data plane: real training under the event clock.

``AsyncTrainer`` is the data-plane counterpart of
``core/sim.AsyncBufferScheduler``: the scheduler decides *when* a
worker's download / compute / upload events fire; the trainer decides
*what* those events mean for the model.  It threads per-worker model
versions through the system — a worker trains from the (possibly stale)
global version it downloaded, and the master keeps every version that
still has in-flight workers so their deltas can be reproduced exactly.

The actual gradient work is the same jitted path the synchronous round
uses: when an apply fires, the buffered commits are grouped by model
version and every group of the apply stacks into ONE
``engine.fused_local_training`` dispatch (``megabatched_local_train``
carries per-worker start params).  Deltas then
flow through the Table-II async verbs — ``CommitDelta`` per worker
(per-edge traffic up the tree) and one ``ApplyBuffered`` (staleness
discount folded into the ``tree_aggregate_groups`` kernel's weight
vector) — so with a full buffer of staleness-0 commits and alpha = 0 the
applied update equals the synchronous round's aggregate to fp tolerance
(tests/test_async.py).

Units and invariants: times are simulated milliseconds from the
scheduler's clock (``t_ms``); payload sizes are bytes (``model_bytes``
and the verbs' ``bytes`` metrics); staleness is counted in model
versions.  Version bookkeeping is refcounted — a snapshot is kept
exactly as long as some in-flight worker may still commit against it
(``_gc_snapshots``), and weight normalization happens once, inside
``ApplyBuffered``'s kernel call, never per level.

The trainer is also the feedback path for utility-based selection
(``fl/selection.UtilitySelector``): at apply time it reports each
client's fresh local loss and delta norm through ``selector.on_train``,
giving the selector its statistical utility term; the scheduler
separately reports observed cycle times (the system term).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro import tracing
from repro.fl import engine
from repro.fl.compression import (
    CompressionPolicy,
    advance_reference,
    as_policy,
    quantize_broadcast_delta,
    quantize_delta,
    reference_grid,
)


@jax.jit
def _add_update(params, update):
    """``params + update`` leaf by leaf, in the params' dtypes: one
    program for the whole tree."""
    return jax.tree.map(lambda p, d: (p + d).astype(p.dtype), params, update)


class AsyncTrainer:
    """Per-app version store + buffered-apply data plane.

    ``apps``: ``fl/rounds.FLApp`` instances (params, shards, hyperparams).
    ``staleness_alpha``: exponent of the 1/(1+s)^a weight discount.
    ``selector``: optional ``fl/selection.ClientSelector`` — fed each
    client's local loss + delta norm at apply time (statistical utility).
    ``compression``: per-app ``CompressionPolicy`` (scalar broadcast or
    list; ``None`` falls back to each ``AppHandle.compression``).  An
    enabled policy quantizes every commit delta (``quantize_delta``)
    under a per-commit rounding key before it enters ``CommitDelta`` —
    the buffered entries then carry ``QuantizedDelta`` wire payloads and
    ``ApplyBuffered`` dequantizes inside the aggregation kernel.  With
    ``error_feedback`` set, each worker's quantization residual
    ``x - deq(q(x))`` is carried into its next commit (EF-SGD), so
    coarse ``levels`` settings stay unbiased over rounds; a failed
    worker loses its residual with the rest of its local state.

    A policy with ``downlink != "none"`` also compresses the broadcast
    direction: the per-version snapshot the workers train from becomes
    the *broadcast state* — ``deq(quantize(params_v))`` for
    ``downlink="qsgd-int8"``, or for ``"delta-qsgd"`` the reference
    reconstruction updated by one fused chain step per apply (kept on
    the device as its grid), with the quantized version delta cached
    (bounded to ``chain_cap`` entries) so stale workers can chain their
    gap.  Every worker at version v holds the same canonical state, so
    version-group megabatching is untouched; the master always
    aggregates into the exact f32 params.
    """

    def __init__(
        self, system, apps, *, staleness_alpha: float = 0.5, replicate: bool = True,
        selector=None, compression=None,
    ):
        self.system = system
        self.apps = list(apps)
        self.staleness_alpha = float(staleness_alpha)
        self.replicate = replicate
        self.selector = selector
        n = len(self.apps)
        if isinstance(compression, (str, CompressionPolicy)):
            compression = [compression] * n
        if compression is None:
            compression = [getattr(a.handle, "compression", None) for a in self.apps]
        assert len(compression) == n
        self._compression = [as_policy(p) for p in compression]
        # monotone per-app commit counter: seeds each commit's rounding
        # key (compression.commit_key) so rounding bits never repeat
        self._commit_seq = [0] * n
        self.version = [0] * n
        self._snapshots = [{0: a.params} for a in self.apps]  # version -> params
        self._refs = [{0: 0} for _ in range(n)]  # version -> in-flight users
        self._worker_version = [dict() for _ in range(n)]  # worker -> version
        self._pending = [[] for _ in range(n)]  # committed (worker, version, seq)
        # EF-SGD residual store: worker -> residual pytree (error_feedback)
        self._ef = [dict() for _ in range(n)]
        # downlink delta-qsgd state: the reference reconstruction the
        # workers hold (== _snapshots[ai][version]) and the bounded
        # version-delta cache, keyed by the version each delta produces
        self._recon = [a.params for a in self.apps]
        self._recon_grid = [None] * n  # the same on the (rows, 256) grid, on the device
        self._delta_cache = [dict() for _ in range(n)]  # version -> QuantizedDelta
        self.history: list[dict] = []

    # -- scheduler hooks -------------------------------------------------------

    def workers(self, ai: int) -> list[int]:
        app = self.apps[ai]
        return [w for w in sorted(app.handle.tree.members) if w in app.data]

    def begin_download(self, ai: int, w: int) -> None:
        """The master transmits the current version to ``w``: pin it."""
        v = self.version[ai]
        self._worker_version[ai][w] = v
        self._refs[ai][v] = self._refs[ai].get(v, 0) + 1

    def commit(self, ai: int, w: int, t: float) -> None:
        """``w``'s upload landed: move it to the apply queue (its delta is
        materialized lazily at apply time, batched with its version peers).
        The commit sequence number is pinned here — delivery order — so a
        worker lapping the buffer twice gets two distinct rounding keys."""
        v = self._worker_version[ai].pop(w)
        seq = self._commit_seq[ai]
        self._commit_seq[ai] += 1
        self._pending[ai].append((w, v, seq))

    def drop(self, ai: int, w: int) -> None:
        """``w`` failed mid-cycle: release its version pin.  Commits it
        already delivered stay buffered — the master has them.  Its
        EF-SGD residual is local state and dies with it."""
        v = self._worker_version[ai].pop(w, None)
        if v is not None:
            self._refs[ai][v] -= 1
        self._ef[ai].pop(w, None)

    def delta_chain(self, ai: int, base: int, target: int) -> list:
        """The cached broadcast deltas reconstructing ``base -> target``
        (one per version step).  Raises ``KeyError`` past the cache
        window — exactly the gap the scheduler prices as a full f32
        fallback download."""
        return [self._delta_cache[ai][v] for v in range(base + 1, target + 1)]

    def _broadcast_state(self, ai: int, params, version: int, policy) -> object:
        """What a worker downloading ``version`` actually receives.

        ``downlink="qsgd-int8"``: the dequantized full-model broadcast.
        ``"delta-qsgd"``: the reference reconstruction — the previous
        reference plus this version's quantized delta, folded in by one
        fused chain step.  Quantizing against the
        *reference* (not the previous exact params) is error feedback on
        the downlink: the reference stays within one quantizer bound of
        the true params at every version, and a worker chaining cached
        deltas from any base lands bit-for-bit on this state.

        delta-qsgd runs on the device (``broadcast_fused``): the reference
        is kept as its grid, the grid program forms ``params - reference``
        on it, the quantized delta is cached with q and scale on the
        device, and the one pull is the held state's.  qsgd-int8 runs on
        the host (``broadcast_eager``)."""
        with tracing.span("broadcast"):
            if policy.downlink == "qsgd-int8":
                tracing.count("broadcast_eager")
                qd = quantize_broadcast_delta(params, policy, app=ai, version=version)
                deq = qd.dequantize()
                return jax.tree.map(
                    lambda p, v: np.asarray(v, dtype=tracing.pull(p).dtype), params, deq
                )
            tracing.count("broadcast_fused")
            ref = self._recon_grid[ai]
            if ref is None:
                ref = reference_grid(
                    [tracing.implicit_push(l) for l in jax.tree.leaves(self._recon[ai])],
                    chunk=int(policy.chunk),
                )
            qd = quantize_broadcast_delta(params, policy, app=ai, version=version, reference=ref)
            cache = self._delta_cache[ai]
            cache[version] = qd
            for v in [v for v in cache if v <= version - int(policy.chain_cap)]:
                del cache[v]
            self._recon_grid[ai], self._recon[ai] = advance_reference(ref, qd, self._recon[ai])
            return self._recon[ai]

    def apply(
        self, ai: int, t: float, *, k: int | None = None, selector_scores=None,
        transport: dict | None = None,
    ) -> dict | None:
        """Buffer is full: train each version group, commit the deltas,
        apply the staleness-weighted update, bump the global version.

        ``k`` (the effective buffer threshold that triggered this apply),
        ``selector_scores`` (the selector's per-client utilities at
        apply time) and ``transport`` (the scheduler's fairness snapshot:
        per-app uplink bytes/throughput and Jain's index) are telemetry
        from the scheduler; they ride into the app handle's
        ``round_records`` via ``ApplyBuffered``.
        """
        with tracing.span("apply", app=ai, version=self.version[ai] + 1):
            app = self.apps[ai]
            pending, self._pending[ai] = self._pending[ai], []
            if not pending:  # commit batch drained (e.g. by churn)
                return None
            cur = self.version[ai]
            groups: dict[int, list[tuple[int, int]]] = {}
            for w, v, seq in pending:
                groups.setdefault(v, []).append((w, seq))
            versions = sorted(groups)
            # every version group of this apply stacks into ONE compiled
            # dispatch: megabatched_local_train carries per-worker start
            # params, so staleness-ragged buffers cost one XLA program
            trained = engine.fused_local_training(
                [(app, [w for w, _ in groups[v]], self._snapshots[ai][v]) for v in versions]
            )
            policy = self._compression[ai]
            losses, loss_weights = [], []
            for v, (deltas, weights, group_losses) in zip(versions, trained):
                ws = groups[v]
                for (w, seq), d, wt, l in zip(ws, deltas, weights, group_losses):
                    payload = d
                    if policy is not None and policy.enabled:
                        target = d
                        if policy.error_feedback:
                            # EF-SGD: fold the worker's carried residual into
                            # this commit before quantizing, then carry the
                            # fresh quantization error forward
                            r = self._ef[ai].get(w)
                            if r is not None:
                                target = jax.tree.map(
                                    lambda a, b: tracing.push(a, jnp.float32) + b, d, r
                                )
                        payload = quantize_delta(target, policy, app=ai, seq=seq)
                        if policy.error_feedback:
                            deq = payload.dequantize()
                            self._ef[ai][w] = jax.tree.map(
                                lambda a, b: tracing.push(a, jnp.float32)
                                - tracing.push(tracing.pull(b), jnp.float32),
                                target, deq,
                            )
                    self.system.CommitDelta(
                        app.handle.app_id, w, payload, weight=wt, staleness=cur - v
                    )
                    losses.append(l)
                    loss_weights.append(wt)
                    if self.selector is not None:
                        loss_val = float(l)
                        if np.isfinite(loss_val):
                            dnorm = 0.0  # loss is the stat signal; skip W host transfers
                        else:
                            dnorm = float(
                                np.sqrt(
                                    sum(
                                        float(np.sum(np.square(tracing.pull(x))))
                                        for x in jax.tree.leaves(d)
                                    )
                                )
                            )
                        self.selector.on_train(ai, w, loss_val, dnorm)
                self._refs[ai][v] -= len(ws)
            stats = self.system.ApplyBuffered(
                app.handle.app_id, staleness_alpha=self.staleness_alpha,
                k=k, selector_scores=selector_scores, transport=transport,
            )
            app.params = _add_update(
                app.params, jax.tree.map(tracing.implicit_push, stats["result"])
            )
            app.round_num += 1
            self.version[ai] = cur + 1
            # the snapshot is what workers RECEIVE for this version: the
            # exact params, or the compressed broadcast state when the
            # downlink axis is on (every worker at a version holds the same
            # canonical state, so version-group training is unchanged)
            held = app.params
            if policy is not None and policy.downlink_enabled:
                held = self._broadcast_state(ai, app.params, cur + 1, policy)
            self._snapshots[ai][cur + 1] = held
            self._refs[ai][cur + 1] = self._refs[ai].get(cur + 1, 0)
            self._gc_snapshots(ai)
            if self.replicate:
                with tracing.span("replicate"):
                    self.system.replicate_master_state(
                        app.handle.app_id, {"round": app.round_num, "version": cur + 1}
                    )
            record = {
                "app_id": app.handle.app_id,
                "t_ms": t,
                "version": cur + 1,
                "arrivals": len(pending),
                "k": k,
                "loss": float(np.average(losses, weights=loss_weights)),
                "mean_staleness": float(np.mean([cur - v for _, v, _ in pending])),
            }
            self.history.append(record)
            app.history.append(record)
            return record

    def _gc_snapshots(self, ai: int) -> None:
        """Drop param versions no in-flight worker can still reference."""
        cur = self.version[ai]
        for v in [v for v, r in self._refs[ai].items() if r <= 0 and v != cur]:
            self._refs[ai].pop(v)
            self._snapshots[ai].pop(v, None)


def run_async(
    system,
    apps,
    *,
    applies: int,
    buffer_k: int | list[int],
    staleness_alpha: float = 0.5,
    model_bytes: float,
    compute_ms=50.0,
    base_ms: float = 5.0,
    churn=None,
    barrier: bool = False,
    adaptive: bool = False,
    adaptive_kwargs: dict | None = None,
    selector=None,
    app_weights=None,
    app_rate_caps=None,
    relay_admission=None,
    compression=None,
    congestion_mode: str = "exact",
    hot_threshold: int = 4,
    resample_every: float | None = None,
    resample_events: int | None = None,
    resample_target_error: float | None = None,
    placement=None,
    max_events: int = 1_000_000,
) -> dict:
    """Wire an ``AsyncTrainer`` under an ``AsyncBufferScheduler`` and run
    every app to ``applies`` buffered updates.  Returns the scheduler
    apply events, churn log, and the trainer's loss-vs-simtime history.

    ``adaptive=True`` turns on per-app ``AdaptiveKController``s
    (``buffer_k`` seeds K); ``selector`` plugs a
    ``fl/selection.ClientSelector`` into both the scheduler (admission,
    cycle-time feedback) and the trainer (loss/delta-norm feedback).
    Transfers are priced as weighted-fair flows; ``app_weights`` /
    ``app_rate_caps`` bias or bound per-app uplink shares, and
    ``relay_admission`` (a ``core.sim.RelayAdmission``) defers stale
    commits at contended relays.

    ``compression`` (a ``fl/compression.CompressionPolicy``, kind string,
    per-app list, or ``None`` for the handles' ``compression`` fields)
    turns on commit-direction quantization: the trainer serializes each
    delta to a ``QuantizedDelta`` and the scheduler prices commit legs
    at the compressed wire size (docs/performance.md "compressed
    transport").  A policy's ``downlink`` axis additionally compresses
    broadcasts — the scheduler prices each download at the worker's
    delta-chain (or fallback) size and the trainer serves the matching
    broadcast state (docs/performance.md "compressed downlink");
    ``error_feedback`` carries per-worker EF-SGD residuals across
    commits.

    Scale knobs (docs/performance.md "scale layer"):
    ``congestion_mode="sampled"`` prices cold cycles
    statistically with ``hot_threshold`` selecting which uplinks stay
    exact, and ``resample_every`` (simulated ms) / ``resample_events``
    (dispatch count) periodically re-price in-flight cold cycles against
    current loads; ``max_events`` raises the event budget for large
    scale runs.  ``resample_target_error`` makes the sampled-congestion
    cadence adaptive (tighten/relax around a target apply-time drift).

    ``placement`` (a ``core.pathplan.PlacementEngine`` or ``True`` for
    defaults) turns on live utility-aware placement: replans on churn /
    defer / contention triggers, re-grafts through the forest's batched
    moves, and feeds selector defer-attribution back into the planner
    (docs/architecture.md "placement layer").  ``None`` (default) keeps
    static placement with byte-identical traces."""
    from repro.core.sim import AsyncBufferScheduler

    trainer = AsyncTrainer(
        system, apps, staleness_alpha=staleness_alpha, selector=selector,
        compression=compression,
    )
    sched = AsyncBufferScheduler(
        system,
        [a.handle for a in apps],
        model_bytes=model_bytes,
        compute_ms=compute_ms,
        base_ms=base_ms,
        buffer_k=buffer_k,
        churn=churn,
        trainer=trainer,
        barrier=barrier,
        adaptive=adaptive,
        adaptive_kwargs=adaptive_kwargs,
        selector=selector,
        app_weights=app_weights,
        app_rate_caps=app_rate_caps,
        relay_admission=relay_admission,
        app_compression=compression,
        congestion_mode=congestion_mode,
        hot_threshold=hot_threshold,
        resample_every=resample_every,
        resample_events=resample_events,
        resample_target_error=resample_target_error,
        placement=placement,
    )
    events = sched.run(applies, max_events=max_events)
    return {
        "events": events,
        "churn": list(sched.churn_log),
        "history": list(trainer.history),
        "trainer": trainer,
        "scheduler": sched,
    }


def worker_compute_fn(base_ms: float = 40.0, spread: float = 6.0, seed: int = 0):
    """Deterministic heterogeneous edge-compute model: each (app, worker)
    draws a fixed slowdown in [1, spread] from a seeded hash — the same
    worker is always the same straggler, for sync and async alike.  The
    draw is memoized per (app, worker): it is called once per cycle
    event, and re-seeding a Generator each call was a measurable event-
    loop cost at M >= 16 (same values either way)."""

    cache: dict[tuple[int, int], float] = {}

    def per_worker(handle, worker, cycle: int = 0):
        key = (handle.app_id, worker)
        ms = cache.get(key)
        if ms is None:
            rng = np.random.default_rng([seed, handle.app_id, worker])
            ms = cache[key] = base_ms * (1.0 + (spread - 1.0) * float(rng.random()))
        return ms

    return per_worker


def sync_barrier_compute_fn(per_worker):
    """Sync counterpart of a per-worker compute model: the barrier round
    waits for the slowest subscribed worker."""

    def f(handle, round_num):
        members = sorted(handle.tree.members)
        return max((per_worker(handle, w) for w in members), default=0.0)

    return f
