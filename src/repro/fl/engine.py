"""Vectorized FL training engine: every worker of a commit batch trains
in one compiled program.

The seed's round dispatched one jitted ``local_train`` per worker from a
Python loop — W dispatches, W × E sequential SGD steps.  The engine
stacks every worker's shard into padded ``(W, B, ...)`` arrays (mask
marks the padding) and runs the E local steps as a single jitted
``vmap`` over the worker axis.  A masked mean makes each worker's
loss/gradient identical to what its unpadded shard produces, so the
engine matches the per-worker ``small_models.local_train`` loop to fp
tolerance (the tests keep that loop as the oracle).

- **bucketing** — ``fused_local_training`` pads W and B up to
  power-of-two buckets (zero mask rows on phantom workers train to
  exactly-zero deltas, discarded on unstack), so ragged shard stacks hit
  one of O(log W * log B) compiled programs; the per-run jit cache-miss
  count is tracked by ``DISPATCH`` and gated in tests/test_hotpath.py.
- **fusion** — ``megabatched_local_train`` vmaps over *per-worker start
  params* as well, so one call trains workers from different model
  versions — and different apps entirely, when their static config
  (model, steps, lr, mu) matches — in ONE dispatch (per-job unstacking
  of deltas).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.fl import small_models as sm

# THE shape-bucket policy (next power of two), shared with the kernel
# wrappers so training-side and kernel-side bucketing stay in lockstep
from repro.kernels.ops import bucket_size


class DispatchStats:
    """Counts jitted training dispatches and (bucketed) jit cache misses.

    ``dispatches`` = calls into a jitted training entry point;
    ``compiles`` = dispatches whose (entry, static config, padded shape)
    key was never seen since the last ``reset()`` — with bucketing, this
    is O(#buckets) per run instead of O(#distinct ragged shapes)
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


def _masked_ce(logits, y, mask):
    ll = jnp.take_along_axis(jax.nn.log_softmax(logits), y[:, None], axis=1)[:, 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


@partial(jax.jit, static_argnames=("logits_fn", "steps", "lr", "mu"))
def megabatched_local_train(
    params_stack, x, y, mask, *, logits_fn, steps: int, lr: float, mu: float = 0.0
):
    """E local SGD steps with *per-worker start params*: vmap over
    (params, shard) together.

    Every worker row carries its own start params (its FedProx anchor
    too), so any set of same-config jobs stacks into one compiled
    program.  Returns (stacked new params (W, ...), per-worker mean
    loss (W,)).
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


def fused_local_training(jobs: list) -> list:
    """Train many (app, workers, start_params) jobs in as few dispatches
    as possible — the cross-app / cross-version megabatch.

    ``jobs``: list of ``(app, workers, start_params)`` (``start_params``
    ``None`` = ``app.params``).  Jobs whose static training config
    (model, local_steps, lr, mu, feature shape) matches are stacked
    along the worker axis — each worker row carrying its own start
    params — padded to one (W, B) shape bucket, and run through a
    single ``megabatched_local_train`` dispatch; deltas/losses are then
    unstacked per job.  Returns ``[(deltas, weights, losses), ...]``
    aligned with ``jobs``: per worker, in the job's ``workers`` order,
    the model-update pytree, the shard size (FedAvg weight) and the mean
    local loss.  A single job is a batch of one (the sync round).
    """
    with tracing.span("train"):
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
            W = bucket_size(w_tot)
            B = bucket_size(b_max)
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
