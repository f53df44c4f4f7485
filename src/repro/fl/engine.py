"""Vectorized FL round engine: all workers of an app train in one kernel.

The seed's ``run_round`` dispatched one jitted ``local_train`` per worker
from a Python loop — W dispatches, W × E sequential SGD steps.  The
engine stacks every worker's shard into padded ``(W, B, ...)`` arrays
(mask marks the padding) and runs the E local steps as a single jitted
``vmap`` over the worker axis, so one XLA program trains the whole app.
A masked mean makes each worker's loss/gradient identical to what its
unpadded shard produces, so the vectorized path matches the per-worker
reference loop to fp tolerance (see tests/test_engine.py).

Shape-bucketed megabatching (the hot-path PR): ragged shard stacks used
to force one XLA *compile* per distinct (W, B) — at M=16 apps the
backend compiler dominated end-to-end wall-clock (23 s of a 37 s run in
the pre-optimization profile).  Two fixes:

- **bucketing** — ``pack_shards`` pads W and B up to power-of-two
  buckets (zero mask rows on phantom workers train to exactly-zero
  deltas, discarded on unstack), so every ragged stack hits one of
  O(log W * log B) compiled programs; the per-run jit cache-miss count
  is tracked by ``DISPATCH`` and gated in tests/test_hotpath.py.
- **fusion** — ``megabatched_local_train`` vmaps over *per-worker start
  params* as well, so commit batches training from different model
  versions — and different apps entirely, when their static config
  (model, steps, lr, mu) matches — stack into ONE dispatch
  (``fused_local_training``; per-job unstacking of deltas).

``local_training(..., vectorized=False)`` keeps the reference loop both
as the equivalence oracle and as the baseline the engine benchmark
compares against; ``set_bucketing(False)`` restores the exact-shape
pre-optimization packing (the bench_hotpath baseline).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.fl import small_models as sm

_BUCKETED = True  # module default for pack_shards/local_training bucketing


def set_bucketing(on: bool) -> bool:
    """Toggle shape-bucketed packing globally; returns the previous value."""
    global _BUCKETED
    prev, _BUCKETED = _BUCKETED, bool(on)
    return prev


# THE shape-bucket policy (next power of two), shared with the kernel
# wrappers so training-side and kernel-side bucketing stay in lockstep
from repro.kernels.ops import bucket_size  # noqa: E402  (re-export)


class DispatchStats:
    """Counts jitted training dispatches and (bucketed) jit cache misses.

    ``dispatches`` = calls into a jitted training entry point;
    ``compiles`` = dispatches whose (entry, static config, padded shape)
    key was never seen since the last ``reset()`` — with bucketing on,
    this is O(#buckets) per run instead of O(#distinct ragged shapes)
    (cross-checked against jax's own jit cache size in tests).
    """

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.dispatches = 0
        self.compiles = 0
        self._keys: set = set()

    def record(self, key) -> None:
        self.dispatches += 1
        if key not in self._keys:
            self._keys.add(key)
            self.compiles += 1


DISPATCH = DispatchStats()


def pack_shards(
    data_by_worker: dict,
    workers: list[int],
    *,
    b_bucket: int | None = None,
    w_bucket: int | None = None,
):
    """Stack ragged worker shards into padded (W, B, ...) arrays + mask.

    Returns (x, y, mask): x (W, B, *feat) f32, y (W, B) i32, mask (W, B)
    f32 with 1.0 on real examples, 0.0 on padding.  ``b_bucket`` /
    ``w_bucket`` pad the batch / worker axes up to an absolute size
    (phantom workers are all-padding rows: zero mask, zero data — they
    train to exactly-zero deltas).
    """
    if not workers:  # a drained commit batch: empty padded stacks, not max([])
        z = np.zeros((0, 0), np.float32)
        return tracing.push(z), tracing.push(z, jnp.int32), tracing.push(z)
    bs = [len(data_by_worker[w][1]) for w in workers]
    B = max(bs) if bs else 1
    if b_bucket is not None:
        assert b_bucket >= B, (b_bucket, B)
        B = b_bucket
    W = len(workers)
    if w_bucket is not None:
        assert w_bucket >= W, (w_bucket, W)
        W = w_bucket
    x0 = tracing.pull(data_by_worker[workers[0]][0])
    xs = np.zeros((W, B) + x0.shape[1:], np.float32)
    ys = np.zeros((W, B), np.int32)
    mask = np.zeros((W, B), np.float32)
    for i, w in enumerate(workers):
        x, y = data_by_worker[w]
        b = len(y)
        xs[i, :b] = tracing.pull(x, np.float32)
        ys[i, :b] = tracing.pull(y, np.int32)
        mask[i, :b] = 1.0
    return tracing.push(xs), tracing.push(ys), tracing.push(mask)


def _masked_ce(logits, y, mask):
    ll = jnp.take_along_axis(jax.nn.log_softmax(logits), y[:, None], axis=1)[:, 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


@partial(jax.jit, static_argnames=("logits_fn", "steps", "lr", "mu"))
def batched_local_train(global_params, x, y, mask, *, logits_fn, steps: int, lr: float, mu: float = 0.0):
    """E local SGD steps for every worker at once: vmap over the W axis.

    Equivalent to running ``small_models.local_train`` per worker — the
    masked CE mean reproduces each shard's unpadded loss exactly.
    Returns (stacked new params (W, ...), per-worker mean loss (W,)).
    """

    def one_worker(xw, yw, mw):
        def loss_fn(p):
            base = _masked_ce(logits_fn(p, xw), yw, mw)
            if mu > 0:
                prox = sum(
                    jnp.sum(jnp.square(a - b))
                    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(global_params))
                )
                base = base + 0.5 * mu * prox
            return base

        def step(p, _):
            l, g = jax.value_and_grad(loss_fn)(p)
            p = jax.tree.map(lambda w, gg: w - lr * gg, p, g)
            return p, l

        params, losses = jax.lax.scan(step, global_params, None, length=steps)
        return params, jnp.mean(losses)

    return jax.vmap(one_worker)(x, y, mask)


@partial(jax.jit, static_argnames=("logits_fn", "steps", "lr", "mu"))
def megabatched_local_train(
    params_stack, x, y, mask, *, logits_fn, steps: int, lr: float, mu: float = 0.0
):
    """E local SGD steps with *per-worker start params*: vmap over
    (params, shard) together.

    The generalization that makes cross-version and cross-app fusion
    possible: ``batched_local_train`` closes over ONE global params
    pytree, so commit batches training from different model versions
    (or different apps) each needed their own dispatch.  Here every
    worker row carries its own start params (its FedProx anchor too),
    so any set of same-config jobs stacks into one compiled program.
    Returns (stacked new params (W, ...), per-worker mean loss (W,)).
    """

    def one_worker(p0, xw, yw, mw):
        def loss_fn(p):
            base = _masked_ce(logits_fn(p, xw), yw, mw)
            if mu > 0:
                prox = sum(
                    jnp.sum(jnp.square(a - b))
                    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0))
                )
                base = base + 0.5 * mu * prox
            return base

        def step(p, _):
            l, g = jax.value_and_grad(loss_fn)(p)
            p = jax.tree.map(lambda w, gg: w - lr * gg, p, g)
            return p, l

        params, losses = jax.lax.scan(step, p0, None, length=steps)
        return params, jnp.mean(losses)

    return jax.vmap(one_worker)(params_stack, x, y, mask)


def local_training(
    app, workers: list[int], *, vectorized: bool = True, params=None,
    bucketed: bool | None = None,
):
    """Run the app's E local steps on every worker's shard.

    Returns (deltas, weights, losses) with one entry per worker, in
    ``workers`` order — deltas are model-update pytrees, weights the
    shard sizes (FedAvg weighting), losses the mean local losses.
    ``params`` overrides the starting model (the async path trains each
    commit batch from the — possibly stale — version its workers
    downloaded, not from ``app.params``).  ``bucketed`` pads (W, B) to
    power-of-two buckets so ragged shards reuse compiled programs
    (default: the module flag set by ``set_bucketing``).
    """
    if not workers:
        return [], [], []
    start = app.params if params is None else params
    logits_fn = sm.LOGITS[app.model]
    weights = [float(len(app.data[w][1])) for w in workers]
    if not vectorized:
        deltas, losses = [], []
        for w in workers:
            x, y = app.data[w]
            new_p, loss = sm.local_train(
                start, start, x, y,
                logits_fn=logits_fn, steps=app.local_steps, lr=app.lr, mu=app.mu,
            )
            deltas.append(jax.tree.map(lambda a, b: a - b, new_p, start))
            losses.append(float(tracing.pull(loss)))
        return deltas, weights, losses

    if bucketed is None:
        bucketed = _BUCKETED
    W = len(workers)
    if bucketed:
        B = max(len(app.data[w][1]) for w in workers)
        x, y, mask = pack_shards(
            app.data, workers, b_bucket=bucket_size(B), w_bucket=bucket_size(W)
        )
    else:
        x, y, mask = pack_shards(app.data, workers)
    DISPATCH.record(
        ("batched", app.model, app.local_steps, app.lr, app.mu, x.shape)
    )
    new_params, losses = batched_local_train(
        start, x, y, mask,
        logits_fn=logits_fn, steps=app.local_steps, lr=app.lr, mu=app.mu,
    )
    stacked = jax.tree.map(lambda n, p: n - p[None], new_params, start)
    # one device->host transfer per leaf, then cheap numpy row views —
    # per-worker device slicing would cost W x leaves dispatches
    stacked_np = jax.tree.map(tracing.pull, stacked)
    deltas = [jax.tree.map(lambda l, i=i: l[i], stacked_np) for i in range(W)]
    return deltas, weights, [float(l) for l in tracing.pull(losses)[:W]]


def fused_local_training(jobs: list, *, bucketed: bool | None = None) -> list:
    """Train many (app, workers, start_params) jobs in as few dispatches
    as possible — the cross-app / cross-version megabatch.

    ``jobs``: list of ``(app, workers, start_params)`` (``start_params``
    ``None`` = ``app.params``).  Jobs whose static training config
    (model, local_steps, lr, mu, feature shape) matches are stacked
    along the worker axis — each worker row carrying its own start
    params — padded to one (W, B) shape bucket, and run through a
    single ``megabatched_local_train`` dispatch; deltas/losses are then
    unstacked per job.  Returns ``[(deltas, weights, losses), ...]``
    aligned with ``jobs``.
    """
    with tracing.span("train"):
        if bucketed is None:
            bucketed = _BUCKETED
        results: list = [None] * len(jobs)
        groups: dict[tuple, list[int]] = {}
        for j, (app, workers, start) in enumerate(jobs):
            if not workers:
                results[j] = ([], [], [])
                continue
            feat = tracing.pull(app.data[workers[0]][0]).shape[1:]
            if start is None:
                start = app.params
            # the param treedef + leaf shapes are part of the fusion key:
            # two apps may share a model NAME (and feat/steps/lr/mu) while
            # differing in num_classes or hidden sizes, and stacking those
            # into one params buffer would be a shape error
            params_sig = (
                jax.tree.structure(start),
                tuple(np.shape(l) for l in jax.tree.leaves(start)),
            )
            key = (app.model, app.local_steps, app.lr, app.mu, feat, params_sig)
            groups.setdefault(key, []).append(j)

        for key, idxs in groups.items():
            model, steps, lr, mu, feat, _params_sig = key
            logits_fn = sm.LOGITS[model]
            w_tot = sum(len(jobs[j][1]) for j in idxs)
            b_max = max(
                len(jobs[j][0].data[w][1]) for j in idxs for w in jobs[j][1]
            )
            W = bucket_size(w_tot) if bucketed else w_tot
            B = bucket_size(b_max) if bucketed else b_max
            with tracing.span("train.pack"):
                xs = np.zeros((W, B) + feat, np.float32)
                ys = np.zeros((W, B), np.int32)
                mask = np.zeros((W, B), np.float32)
                row = 0
                spans = []  # (job index, row offset, worker count)
                for j in idxs:
                    app, workers, _ = jobs[j]
                    spans.append((j, row, len(workers)))
                    for w in workers:
                        x, yv = app.data[w]
                        b = len(yv)
                        xs[row, :b] = tracing.pull(x, np.float32)
                        ys[row, :b] = tracing.pull(yv, np.int32)
                        mask[row, :b] = 1.0
                        row += 1
                # per-row start params; phantom rows reuse the first job's params
                # (zero mask -> zero grads -> exactly-zero deltas, discarded)
                first = jobs[idxs[0]][2]
                if first is None:
                    first = jobs[idxs[0]][0].params
                leaves0, treedef = jax.tree.flatten(first)
                rows_per_leaf = [
                    np.empty((W,) + np.shape(l), tracing.pull(l).dtype) for l in leaves0
                ]
                for j, off, count in spans:
                    start = jobs[j][2] if jobs[j][2] is not None else jobs[j][0].params
                    for arr, leaf in zip(rows_per_leaf, jax.tree.leaves(start)):
                        arr[off : off + count] = tracing.pull(leaf)
                for arr, leaf in zip(rows_per_leaf, leaves0):
                    arr[row:] = tracing.pull(leaf)
                params_stack = jax.tree.unflatten(
                    treedef, [tracing.push(a) for a in rows_per_leaf]
                )
                xs_d, ys_d, mask_d = tracing.push(xs), tracing.push(ys), tracing.push(mask)
            DISPATCH.record(("mega", model, steps, lr, mu, xs.shape, _params_sig))
            new_params, losses = megabatched_local_train(
                params_stack, xs_d, ys_d, mask_d,
                logits_fn=logits_fn, steps=steps, lr=lr, mu=mu,
            )
            stacked = jax.tree.map(lambda n, p: n - p, new_params, params_stack)
            stacked_np = jax.tree.map(tracing.pull, stacked)
            losses_np = tracing.pull(losses)
            for j, off, count in spans:
                app, workers, _ = jobs[j]
                deltas = [
                    jax.tree.map(lambda l, i=off + i: l[i], stacked_np)
                    for i in range(count)
                ]
                weights = [float(len(app.data[w][1])) for w in workers]
                results[j] = (deltas, weights, [float(l) for l in losses_np[off : off + count]])
        return results


def run_round(system, app, *, use_kernel: bool = True, vectorized: bool = True) -> dict:
    """One Totoro+ round through the Table-II verbs; returns metrics.

    Broadcast down the tree, vectorized local training, hierarchical
    kernel aggregation up the tree (``TotoroSystem.Aggregate`` executes
    the level schedule), master server-update + state replication.
    """
    bstats = system.Broadcast(app.handle.app_id, app.params)

    tree = app.handle.tree
    workers = [w for w in sorted(tree.members) if w in app.data]
    deltas, weights, losses = local_training(app, workers, vectorized=vectorized)

    astats = system.Aggregate(
        app.handle.app_id,
        {w: d for w, d in zip(workers, deltas)},
        weights={w: wt for w, wt in zip(workers, weights)},
        use_kernel=use_kernel,
    )
    agg = astats["result"]

    app.params = jax.tree.map(lambda p, d: (p + d).astype(p.dtype), app.params, agg)
    app.round_num += 1
    system.replicate_master_state(app.handle.app_id, {"round": app.round_num})

    metrics = {
        "round": app.round_num,
        "loss": float(np.mean(losses)),
        "time_ms": bstats["time_ms"] + astats["time_ms"],
        "traffic_bytes": bstats["bytes"] + astats["bytes"],
        "agg_levels": astats.get("levels", []),
    }
    app.history.append(metrics)
    return metrics


def run_round_fused(system, apps: list, *, use_kernel: bool = True) -> list[dict]:
    """One round for MANY apps with a single fused training dispatch.

    The multi-app analogue of ``run_round``: every app Broadcasts, then
    all apps' workers train together through ``fused_local_training``
    (same-config apps stack into one megabatched vmap; deltas unstack
    per app), then each app Aggregates and applies its server update.
    Semantics per app match ``run_round`` to fp tolerance; dispatches
    per round drop from M to the number of distinct static configs.
    Returns one metrics dict per app, in ``apps`` order.
    """
    bstats_all, jobs = [], []
    for app in apps:
        bstats_all.append(system.Broadcast(app.handle.app_id, app.params))
        tree = app.handle.tree
        workers = [w for w in sorted(tree.members) if w in app.data]
        jobs.append((app, workers, app.params))
    trained = fused_local_training(jobs)

    out = []
    for app, bstats, (_, workers, _), (deltas, weights, losses) in zip(
        apps, bstats_all, jobs, trained
    ):
        astats = system.Aggregate(
            app.handle.app_id,
            {w: d for w, d in zip(workers, deltas)},
            weights={w: wt for w, wt in zip(workers, weights)},
            use_kernel=use_kernel,
        )
        agg = astats["result"]
        app.params = jax.tree.map(
            lambda p, d: (p + d).astype(p.dtype), app.params, agg
        )
        app.round_num += 1
        system.replicate_master_state(app.handle.app_id, {"round": app.round_num})
        metrics = {
            "round": app.round_num,
            "loss": float(np.mean(losses)) if losses else 0.0,
            "time_ms": bstats["time_ms"] + astats["time_ms"],
            "traffic_bytes": bstats["bytes"] + astats["bytes"],
            "agg_levels": astats.get("levels", []),
        }
        app.history.append(metrics)
        out.append(metrics)
    return out
