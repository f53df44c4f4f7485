"""A plain reference of the buffered-async FL apply, for ``correct``.

It imports nothing of the program and takes nothing the program made.
From the benchmark it takes the initial weights and the data (both drawn
by ``bench/lib/fixture.py`` from the seed) and the schedule the
benchmark's own probes recorded: which worker committed, which model
version it had downloaded (counted by the benchmark), and the commit's
number within its app.  From these it recomputes, in straightforward
``jax.numpy`` at the matmul precision the configuration states
(``matmul_precision``, one bfloat16 pass in the deployment's file):

1. local training: ``steps`` SGD steps at ``lr`` on the worker's whole
   shard, under the plain loss of the model's kind
   (``bench/models/<kind>.py``), from the state the worker downloaded;
   the commit is new minus start;
2. commit quantization (``commit="qsgd-int8"``): the update's leaves in
   wire order (jax's flatten order: by key, nested dicts depth first)
   are concatenated, zero-padded to rows of 256, and each row is rounded
   stochastically to ``levels`` steps of ``max|row| / levels``; the rounding draws ``uniform(key, (rows,
   256))`` with ``key = fold_in(fold_in(PRNGKey(seed), app), commit)``;
3. buffered aggregation: commit weights ``shard / (1 + staleness) **
   alpha`` with staleness the apply's version minus the commit's,
   the weighted mean added to the global weights;
4. the broadcast chain (``broadcast="delta-qsgd"``): the workers hold a
   reconstruction ``R``; after each apply the delta ``P - R`` is rounded
   as in 2 at ``broadcast_levels`` under ``fold_in(fold_in(fold_in(
   PRNGKey(seed), 0x0D0C), app), version)`` and added to ``R``; a
   worker downloading that version trains from ``R``.  Without a
   compressed broadcast the workers train from ``P`` itself.

A kind with a frozen part that every app shares (``shared``, drawn by
the fixture) gets it in every local step as a traced argument; it is
never trained, rounded, aggregated or broadcast, and ``params``,
``held`` and every update hold the trained leaves alone.

``mode`` computes the same thing otherwise, for the control and the
faults (``bench/control.py``): ``"fp8"`` rounds every matmul operand of
local training to float8 (e4m3, one scale per tensor), the step below
the stated bfloat16; ``"bf16"`` trains and aggregates in bfloat16, the
step below the stated float32 storage; ``"int4"`` rounds commits to 7
steps instead of 127;
``"frozen"`` leaves the weights unchanged by each apply; ``"half"``
trains each worker on the first half of its shard; ``"altered"``
negates the first commit of each apply.  The frozen part is rounded
only as ``mm`` and ``dtype`` round it in the kind's ``loss``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.spec import model_kind

MODES = ("sound", "fp8", "bf16", "int4", "frozen", "half", "altered")
BROADCAST_LANE = 0x0D0C
CHUNK = 256


@dataclass
class AppResult:
    """One app after each followed apply: weights, mean local loss and
    the state a worker downloading that version trains from."""

    params: list    # per apply: the weights' pytree of np.ndarray
    losses: list    # per apply: float
    held: list      # per apply: the same pytree


def _fp8(a):
    """``a`` rounded to float8 e4m3 under one scale for the tensor; the
    gradient passes through unrounded."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    r = (a / s).astype(jnp.float8_e4m3fn).astype(a.dtype) * s
    return a + jax.lax.stop_gradient(r - a)


def _cast(tree, dtype):
    """The float leaves of ``tree`` in ``dtype``; other leaves as they are."""
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def _samples(batch) -> int:
    return int(jax.tree.leaves(batch)[0].shape[0])


@partial(jax.jit, static_argnames=("loss", "steps", "lr", "dtype", "fp8"))
def _local_sgd(p0, batch, shared=None, *, loss, steps: int, lr: float, dtype: str,
               fp8: bool = False):
    """``steps`` SGD steps from ``p0`` on ``batch`` under the kind's
    ``loss``, which also reads the frozen ``shared`` where the kind has
    one; returns the update in float32 and the mean of the step losses."""
    dt = jnp.dtype(dtype)
    mm = (lambda a, b: _fp8(a) @ _fp8(b)) if fp8 else (lambda a, b: a @ b)
    frozen = {} if shared is None else {"shared": shared}
    p = _cast(p0, dt)

    losses = []
    start = p
    for _ in range(steps):
        value, grad = jax.value_and_grad(lambda q: loss(q, batch, mm=mm, dtype=dt, **frozen))(p)
        p = jax.tree.map(lambda a, g: a - jnp.asarray(lr, dt) * g, p, grad)
        losses.append(value.astype(jnp.float32))
    update = jax.tree.map(lambda a, s: (a - s).astype(jnp.float32), p, start)
    return update, jnp.mean(jnp.stack(losses))


@partial(jax.jit, static_argnames=("levels",))
def _round_rows(flat, key, *, levels: int):
    """Stochastic rounding of ``flat`` on rows of 256 (see module doc);
    returns the dequantized values, same length as ``flat``."""
    n = flat.shape[0]
    rows = max(1, math.ceil(n / CHUNK))
    x = jnp.zeros((rows * CHUNK,), jnp.float32).at[:n].set(flat).reshape(rows, CHUNK)
    u = jax.random.uniform(key, (rows, CHUNK), jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True) / levels, 1e-12)
    q = jnp.floor(x / scale + u)
    return (q * scale).reshape(-1)[:n]


def _flat(tree):
    return jnp.concatenate([jnp.ravel(a).astype(jnp.float32) for a in jax.tree.leaves(tree)])


def _unflat(vec, like):
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for a in leaves:
        size = int(np.prod(a.shape))
        out.append(vec[off:off + size].reshape(a.shape))
        off += size
    return jax.tree.unflatten(treedef, out)


def commit_key(seed: int, app: int, commit: int):
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), app), commit)


def broadcast_key(seed: int, app: int, version: int):
    lane = jax.random.fold_in(jax.random.PRNGKey(seed), BROADCAST_LANE)
    return jax.random.fold_in(jax.random.fold_in(lane, app), version)


def follow(*, app: int, params0: dict, data: dict, schedule: list, config: dict,
           traffic: dict, policy_seed: int, mode: str = "sound", shared=None) -> AppResult:
    """Replay one app's followed applies; see the module docstring."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    comp = traffic["compression"]
    commit_kind, broadcast = comp["commit"], comp["broadcast"]
    levels = 7 if mode == "int4" else int(comp.get("levels", 127))
    b_levels = int(comp.get("broadcast_levels", 7))
    dtype = "bfloat16" if mode == "bf16" else "float32"
    steps, lr = int(config["local_steps"]), float(config["lr"])
    alpha = float(config["staleness_alpha"])
    loss_fn = model_kind(config["model"]).loss

    with jax.default_matmul_precision(str(config["matmul_precision"])):
        p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params0)
        recon = p
        held = {0: p}
        out = AppResult([], [], [])
        for v, commits in enumerate(schedule):
            updates, weights, stale, losses = [], [], [], []
            for i, (worker, base, seq) in enumerate(sorted(commits, key=lambda c: c[1])):
                batch = data[worker]
                if mode == "half":
                    half = _samples(batch) // 2
                    batch = jax.tree.map(lambda a: a[:half], batch)
                upd, loss = _local_sgd(held[base], jax.tree.map(jnp.asarray, batch), shared,
                                       loss=loss_fn, steps=steps, lr=lr, dtype=dtype,
                                       fp8=mode == "fp8")
                u = _flat(upd)
                if commit_kind == "qsgd-int8":
                    u = _round_rows(u, commit_key(policy_seed, app, seq), levels=levels)
                elif mode == "bf16":
                    u = u.astype(jnp.bfloat16).astype(jnp.float32)
                if mode == "altered" and i == 0:
                    u = -u
                updates.append(u)
                weights.append(float(_samples(data[worker])))
                stale.append(v - base)
                losses.append(float(loss))
            w = jnp.asarray(weights, jnp.float32) * (
                1.0 + jnp.asarray(stale, jnp.float32)) ** (-alpha)
            if mode == "bf16":
                stack = jnp.stack(updates).astype(jnp.bfloat16)
                agg = (jnp.sum(w.astype(jnp.bfloat16)[:, None] * stack, axis=0)
                       / jnp.sum(w).astype(jnp.bfloat16)).astype(jnp.float32)
            else:
                agg = jnp.sum(w[:, None] * jnp.stack(updates), axis=0) / jnp.sum(w)
            if mode != "frozen":
                p = jax.tree.map(jnp.add, p, _unflat(agg, p))
                if mode == "bf16":
                    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), p)
            if broadcast == "delta-qsgd":
                delta = _flat(p) - _flat(recon)
                step = _round_rows(delta, broadcast_key(policy_seed, app, v + 1),
                                   levels=b_levels)
                recon = jax.tree.map(jnp.add, recon, _unflat(step, recon))
                held[v + 1] = recon
            elif broadcast == "none":
                held[v + 1] = p
            else:
                raise ValueError(f"broadcast {broadcast!r} has no reference")
            out.params.append(jax.tree.map(np.asarray, p))
            out.losses.append(float(np.average(losses, weights=weights)))
            out.held.append(jax.tree.map(np.asarray, held[v + 1]))
    return out
