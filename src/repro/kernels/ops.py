"""Public jit'd wrappers for the Pallas kernels, with a compiled fallback.

Two execution paths per kernel (selected by ``kernel_mode()``):

- **Pallas** — the TPU target.  On TPU the kernels compile through
  Mosaic; off-TPU the same source runs in ``interpret=True`` mode, which
  executes the kernel body per grid point at Python speed.  Interpret
  mode is the correctness anchor, not a production path — it made every
  CPU aggregation call a simulator hot spot.
- **Compiled jnp fallback** — the ``ref.py`` oracles (the kernels'
  correctness contract) jitted directly, selected automatically whenever
  the Pallas path would have interpreted (``mode="auto"``, the default).
  The update kernel donates its parameter buffer so the fallback is an
  in-place read-modify-write like the fused Pallas kernel.

Modes: ``auto`` (jnp off-TPU, Pallas on TPU), ``pallas`` (always Pallas
— interpret off-TPU; the pre-optimization behavior, kept for parity
tests and benchmark baselines), ``jnp`` (always the compiled fallback).
Set via ``set_kernel_mode`` or the ``REPRO_KERNEL_MODE`` env var.

To keep recompiles at O(#buckets) instead of O(#distinct shapes), the
batched-group wrapper pads the group and child dims up to power-of-two
buckets with zero-weight, zero-valued slots; appending exact float zeros
to a weighted sum never changes the partial sums, so bucketing is
bit-exact (asserted in tests/test_hotpath.py).  Wrappers also handle
tile padding and pytree-level application as before.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import broadcast as _bc
from . import fused_update as _fu
from . import policy_update as _pu
from . import quantize as _q
from . import ref as _ref
from . import tree_aggregate as _ta

_VALID_MODES = ("auto", "pallas", "jnp")
_MODE = os.environ.get("REPRO_KERNEL_MODE", "auto")
if _MODE not in _VALID_MODES:
    raise ValueError(f"REPRO_KERNEL_MODE must be one of {_VALID_MODES}, got {_MODE!r}")


def kernel_mode() -> str:
    return _MODE


def set_kernel_mode(mode: str) -> str:
    """Select the kernel execution path; returns the previous mode."""
    global _MODE
    if mode not in _VALID_MODES:
        raise ValueError(f"kernel mode must be one of {_VALID_MODES}, got {mode!r}")
    prev, _MODE = _MODE, mode
    return prev


def _use_jnp() -> bool:
    if _MODE == "jnp":
        return True
    if _MODE == "pallas":
        return False
    return jax.default_backend() != "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def bucket_size(n: int) -> int:
    """Next power of two >= n (>= 1): THE shape-bucket policy, shared by
    the kernel wrappers here and the training engine (``fl/engine.py``
    re-exports it) so the two sides can never desynchronize.  Padding
    cost is bounded below 2x elements per axis, in exchange for O(log)
    distinct compiled programs per dimension."""
    return 1 << (max(1, int(n)) - 1).bit_length()


_bucket = bucket_size  # internal alias used by the wrappers below


def _pad_to(x: jax.Array, mult: int, axis: int = 0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def _pad_axis_to(x, size: int, axis: int):
    """Zero-pad one axis up to an absolute size (no-op when already there)."""
    if x.shape[axis] == size:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, widths)


def tree_aggregate(grads: jax.Array, weights: jax.Array) -> jax.Array:
    """(C, L) x (C,) -> (L,) f32 weighted sum (pads L to the tile size)."""
    if _use_jnp():
        c = _bucket(grads.shape[0])
        g = _pad_axis_to(grads, c, 0)
        w = _pad_axis_to(weights, c, 0)
        return _ta.tree_aggregate_jnp(g, w)
    g, pad = _pad_to(grads, _ta.TILE, axis=1)
    out = _ta.tree_aggregate(g, weights, interpret=_interpret())
    return out[: grads.shape[1]]


def tree_aggregate_groups(grads: jax.Array, weights: jax.Array) -> jax.Array:
    """(G, C, L) x (G, C) -> (G, L): one tree level as G padded groups.

    The compiled fallback buckets G and C to powers of two with
    zero-weight phantom slots, so every level of every tree hits one of
    O(log G * log C) compiled programs per L instead of one per exact
    shape.
    """
    if _use_jnp():
        gb, cb = _bucket(grads.shape[0]), _bucket(grads.shape[1])
        g = _pad_axis_to(_pad_axis_to(grads, gb, 0), cb, 1)
        w = _pad_axis_to(_pad_axis_to(weights, gb, 0), cb, 1)
        return _ta.tree_aggregate_groups_jnp(g, w)[: grads.shape[0]]
    g, pad = _pad_to(grads, _ta.TILE, axis=2)
    out = _ta.tree_aggregate_groups(g, weights, interpret=_interpret())
    return out[:, : grads.shape[2]]


def _stack_pytrees(updates: list) -> jax.Array:
    """(C, L) f32 stack of flattened update pytrees."""
    return jnp.stack([
        jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in jax.tree.leaves(u)])
        for u in updates
    ])


def _unflatten_like(vec: jax.Array, like) -> object:
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for l in leaves:
        out.append(vec[off : off + l.size].reshape(l.shape))
        off += l.size
    return jax.tree.unflatten(treedef, out)


def tree_aggregate_pytree(updates: list, weights) -> object:
    """Aggregate a list of model-update pytrees with the kernel."""
    w = jnp.asarray(weights, jnp.float32)
    agg = tree_aggregate(_stack_pytrees(updates), w)
    return _unflatten_like(agg, updates[0])


def buffered_aggregate(updates: list, weights, staleness, *, alpha: float = 0.5):
    """Staleness-weighted buffered aggregate (async FedBuff apply).

    The K buffered deltas form ONE (1, K, L) group through the batched
    ``tree_aggregate_groups`` kernel with the staleness discount
    ``w_i / (1+s_i)^alpha`` folded into its weight vector; the weighted
    sum is normalized by the combined weight so a full uniform-staleness
    buffer at alpha's no-op point matches synchronous FedAvg exactly.
    K rides the group wrapper's child-dim bucketing, so varying buffer
    fills (adaptive K, churn-clamped applies) reuse one compiled program
    per bucket.

    Returns (aggregate pytree, combined weights (K,) f32).
    """
    w = _ta.staleness_weights(weights, staleness, alpha)
    stacked = _stack_pytrees(updates)[None]  # (1, K, L)
    agg = tree_aggregate_groups(stacked, w[None])[0] / jnp.maximum(w.sum(), 1e-12)
    return _unflatten_like(agg, updates[0]), w


@jax.jit
def _quantized_operands(q, s, w):
    """The aggregation kernel's operands from K quantized deltas, in one
    program: the lattice points as the (R, K, C) f32 group layout and
    the per-(row, worker) weights ``w_k * s_{k,r}``, with ``w`` (K,) the
    staleness-discounted weights."""
    q, s = jnp.asarray(q), jnp.asarray(s)  # (K, R, C), (K, R, 1)
    g = jnp.transpose(q.astype(jnp.float32), (1, 0, 2))  # (R, K, C)
    gw = jnp.transpose(w[:, None] * s.reshape(s.shape[0], -1))  # (R, K)
    return g, gw


@functools.partial(jax.jit, static_argnames=("shapes",))
def _normalised(agg, w, *, shapes=None):
    """The weighted sum over the combined weight, raveled; with
    ``shapes`` cut into leaves of those shapes (flatten order, the grid's
    zero padding dropped)."""
    flat = jnp.ravel(agg / jnp.maximum(w.sum(), 1e-12))
    if shapes is None:
        return flat
    leaves, off = [], 0
    for shape in shapes:
        size = math.prod(shape)
        leaves.append(flat[off : off + size].reshape(shape))
        off += size
    return tuple(leaves)


def buffered_aggregate_quantized(qs, scales, weights, staleness, *, alpha: float = 0.5,
                                 shapes: tuple | None = None):
    """Staleness-weighted aggregate of K *quantized* deltas, dequantized
    inside the aggregation (the compressed-transport apply path).

    ``qs``: K int8 arrays (R, C) — each worker's flattened delta on the
    QSGD lattice — or their (K, R, C) stack; ``scales``: K f32 arrays
    (R, 1) — the per-chunk max-abs scales — or their (K, R, 1) stack.
    Instead of dequantizing each delta and re-running
    ``buffered_aggregate``, the per-row scale composes with the
    staleness discount into ONE weight per (row, worker):

        agg[r, :] = sum_k (w_k * s_{k,r}) * q_k[r, :] / sum_k w_k

    where ``w_k = weight_k / (1+staleness_k)^alpha`` — exactly the
    unfused ``buffered_aggregate(dequantize(q_k * s_k), ...)`` result
    (linearity; checked to fp tolerance in tests/test_compression.py).
    The R rows form the group axis of ``tree_aggregate_groups``, so the
    fused path rides the same Pallas kernel / compiled fallback and the
    same shape buckets as the uncompressed apply.

    The staleness discount (``tree_aggregate.staleness_weights``, called
    from the host as the async path's one change to the math), then one
    program builds the kernel's operands, the kernel runs as its own
    dispatch, and one program normalizes.  Every operand stays on the
    device.

    Returns (aggregate, combined weights (K,) f32): the aggregate flat
    (R*C,) f32, or with ``shapes`` (the delta's leaf shapes) the tuple of
    its leaves.
    """
    w = _ta.staleness_weights(weights, staleness, alpha)  # (K,)
    g, gw = _quantized_operands(qs, scales, w)
    return _normalised(tree_aggregate_groups(g, gw), w, shapes=shapes), w


def jain_fairness(x) -> float:
    """Jain's fairness index ``(sum x)^2 / (n * sum x^2)`` in (0, 1].

    Host-side telemetry used by the async scheduler's fairness log and
    ``benchmarks/bench_fairness.py``: x is a vector of per-app uplink
    throughputs (or progress rates); 1.0 means a perfectly even split,
    ``1/n`` means one app holds everything.  An empty or all-zero vector
    scores 1.0 (nothing to be unfair about)."""
    v = np.asarray(x, np.float64)
    if v.size == 0:
        return 1.0
    q = float(np.sum(v * v))
    if q <= 0.0:
        return 1.0
    s = float(np.sum(v))
    return (s * s) / (v.size * q)


@functools.partial(jax.jit, static_argnames=("levels",))
def _qsgd_quantize_jnp(x, rand, levels=127):
    return _ref.quantize_ref(x, rand, levels=levels)


@functools.partial(jax.jit)
def _qsgd_dequantize_jnp(q, scale):
    return _ref.dequantize_ref(q, scale)


def qsgd_quantize(x: jax.Array, rand: jax.Array, *, levels: int = 127):
    """(R, 256) -> (int8, scales), any R: one dispatch of the kernel's
    own program, which takes the ragged last row block itself.
    ``levels`` (static) is the per-sign lattice size (<= 127)."""
    if _use_jnp():
        return _qsgd_quantize_jnp(x, rand, levels=levels)
    return _q.qsgd_quantize(x, rand, interpret=_interpret(), levels=levels)


def qsgd_dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    if _use_jnp():
        return _qsgd_dequantize_jnp(q, scale)
    qp, pad = _pad_to(q, _q.ROWS_PER_BLOCK, axis=0)
    sp, _ = _pad_to(scale, _q.ROWS_PER_BLOCK, axis=0)
    out = _q.qsgd_dequantize(qp, sp, interpret=_interpret())
    return out[: q.shape[0]]


@jax.jit
def _apply_quantized_jnp(w, q, s):
    return _ref.apply_quantized_ref(w, q, s)


def apply_quantized_broadcast(w: jax.Array, q: jax.Array, scale: jax.Array) -> jax.Array:
    """Fused dequantize-and-apply of a broadcast delta chain: (R, 256)
    f32 held params + (D, R, 256) int8 lattice points * (D, R, 1) f32
    per-chunk scales -> (R, 256) f32, the chain accumulated strictly in
    order in one pass (docs/performance.md "compressed downlink").  Pads
    rows to the block size; the chain axis D (<= ``chain_cap``) is a
    static unroll, so distinct chain lengths compile O(chain_cap)
    programs total."""
    w, q, scale = jnp.asarray(w), jnp.asarray(q), jnp.asarray(scale)
    if _use_jnp():
        return _apply_quantized_jnp(w, q, scale)
    wp, _ = _pad_to(w, _bc.ROWS_PER_BLOCK, axis=0)
    qp, _ = _pad_to(q, _bc.ROWS_PER_BLOCK, axis=1)
    sp, _ = _pad_to(scale, _bc.ROWS_PER_BLOCK, axis=1)
    out = _bc.apply_quantized_broadcast(wp, qp, sp, interpret=_interpret())
    return out[: w.shape[0]]


@functools.partial(jax.jit, static_argnames=("tau", "alpha", "beta"))
def _policy_update_jnp(pi, mask, cand, reward_sums, *, tau, alpha, beta):
    return _ref.policy_update_ref(
        pi, mask, cand, reward_sums, tau=tau, alpha=alpha, beta=beta
    )


def policy_update(pi, mask, cand, reward_sums, *, tau: int, alpha: float, beta: float):
    """(N,K) policies -> updated policies (pads N to the node block)."""
    if _use_jnp():
        return _policy_update_jnp(
            pi, mask, cand, reward_sums, tau=tau, alpha=alpha, beta=beta
        )
    N = pi.shape[0]
    pi_p, _ = _pad_to(pi, _pu.NODE_BLOCK, axis=0)
    # padded nodes get a valid uniform row to avoid 0/0
    if pi_p.shape[0] != N:
        pad_rows = pi_p.shape[0] - N
        K = pi.shape[1]
        pi_p = pi_p.at[N:].set(1.0 / K)
    mask_p, _ = _pad_to(mask.astype(jnp.float32), _pu.NODE_BLOCK, axis=0)
    mask_p = mask_p.at[N:].set(1.0) if mask_p.shape[0] != N else mask_p
    rs_p, _ = _pad_to(reward_sums, _pu.NODE_BLOCK, axis=0)
    out = _pu.policy_update(
        pi_p, mask_p > 0, cand, rs_p, tau=tau, alpha=alpha, beta=beta,
        interpret=_interpret(),
    )
    return out[:N]


@functools.partial(jax.jit, static_argnames=("lr", "mu", "wd"))
def _fused_update_jnp(w, g, w0, *, lr, mu, wd):
    return _ref.fused_update_ref(w, g, w0, lr, mu, wd)


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("lr", "mu", "wd")
)
def _fused_update_jnp_donated(w, g, w0, *, lr, mu, wd):
    # the parameter buffer is donated: like the Pallas kernel's VMEM
    # read-modify-write, the fallback updates w in place instead of
    # allocating a second full parameter vector
    return _ref.fused_update_ref(w, g, w0, lr, mu, wd)


def fused_update(
    w, g, w0, *, lr: float, mu: float = 0.0, wd: float = 0.0, donate: bool = False
):
    """Flattened fused FedProx/SGD update (pads to the tile size).

    ``donate=True`` (compiled-fallback path) donates ``w``'s buffer to
    the update — the in-place read-modify-write a server update wants —
    so the caller MUST NOT touch ``w`` afterwards (and ``w0`` must not
    alias it; pass ``donate=False``, the default, for the reference
    semantics where ``w`` stays valid).
    """
    shape, dtype = w.shape, w.dtype
    if _use_jnp():
        fn = _fused_update_jnp_donated if donate else _fused_update_jnp
        out = fn(jnp.ravel(w), jnp.ravel(g), jnp.ravel(w0), lr=lr, mu=mu, wd=wd)
        return out.reshape(shape).astype(dtype)
    wf, _ = _pad_to(w.ravel(), _fu.TILE)
    gf, _ = _pad_to(g.ravel(), _fu.TILE)
    w0f, _ = _pad_to(w0.ravel(), _fu.TILE)
    out = _fu.fused_update(wf, gf, w0f, lr=lr, mu=mu, wd=wd, interpret=_interpret())
    return out[: w.size].reshape(shape).astype(dtype)


def fused_update_pytree(params, grads, round_start, *, lr, mu=0.0, wd=0.0, donate=False):
    return jax.tree.map(
        lambda w, g, w0: fused_update(w, g, w0, lr=lr, mu=mu, wd=wd, donate=donate),
        params, grads, round_start,
    )
