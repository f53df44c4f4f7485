"""Pallas TPU kernel: Algorithm-1 episode policy update, batched over nodes.

The paper's headline complexity claim (Table I: O(log N * Matmul); Figs
15/16) is that the Totoro+ planner is "parallel matrix multiplications".
This kernel runs lines 5-8 for a block of nodes entirely in VMEM:
min-log-det exploratory policy over the candidate set, importance-weighted
potential gradient (one-hot features => M(pi)^{-1} = diag(1/pi)), the
candidate-argmax via an (NB,K)x(K,M) matmul on the MXU, and the
Frank-Wolfe + exploration mixture.

Block shapes: nodes tiled by NODE_BLOCK; K (hops) and M (candidates) are
small (<= 32/64) and sit fully in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NODE_BLOCK = 64  # (NB, M, K) temporaries stay well inside the 16 MiB VMEM scope


def _select(candn, idx):
    """candn[n, idx[n], :] for every node: a one-hot masked sum over the
    candidate axis (Mosaic lowers only 2D gathers).  Exactly one term of
    each sum is nonzero, so the selection is exact."""
    m = jax.lax.broadcasted_iota(jnp.int32, candn.shape[:2], 1)
    onehot = (m == idx[:, None]).astype(jnp.float32)  # (NB, M)
    return jnp.sum(candn * onehot[:, :, None], axis=1)


def _kernel(alpha_ref, beta_ref, tau_ref, pi_ref, mask_ref, cand_ref, rsum_ref, out_ref):
    alpha = alpha_ref[0]
    beta = beta_ref[0]
    tau = tau_ref[0]
    pi = pi_ref[...]  # (NB, K)
    maskf = mask_ref[...].astype(jnp.float32)
    cand = cand_ref[...]  # (M, K)
    rsum = rsum_ref[...]  # (NB, K)

    # per-node re-masked candidate set: (NB, M, K)
    candn = cand[None, :, :] * maskf[:, None, :]
    candn = candn / jnp.maximum(jnp.sum(candn, axis=-1, keepdims=True), 1e-12)

    # line 5: rho = argmin_det M(lambda); det = prod_k lambda_k (one-hot psi)
    logdet = jnp.sum(
        jnp.where(maskf[:, None, :] > 0, jnp.log(jnp.maximum(candn, 1e-12)), 0.0), axis=-1
    )  # (NB, M)
    rho = _select(candn, jnp.argmin(logdet, axis=-1))

    # line 6: grad = rsum / (tau * pi)
    grad = rsum / (tau * jnp.maximum(pi, 1e-12)) * maskf  # (NB, K)

    # line 7: scores = candn . grad  -> argmax candidate
    scores = jnp.sum(candn * grad[:, None, :], axis=-1)  # (NB, M)
    pi_tilde = _select(candn, jnp.argmax(scores, axis=-1))

    # line 8: Frank-Wolfe + exploration mixture, renormalized on the mask
    pi_new = alpha * (pi + beta * (pi_tilde - pi)) + (1.0 - alpha) * rho
    pi_new = pi_new * maskf
    out_ref[...] = pi_new / jnp.maximum(jnp.sum(pi_new, axis=-1, keepdims=True), 1e-12)


@functools.partial(jax.jit, static_argnames=("tau", "interpret"))
def policy_update(
    pi: jax.Array,  # (N, K) f32
    mask: jax.Array,  # (N, K) bool
    cand: jax.Array,  # (M, K) f32
    reward_sums: jax.Array,  # (N, K) f32
    *,
    tau: int,
    alpha: float,
    beta: float,
    interpret: bool = False,
) -> jax.Array:
    N, K = pi.shape
    assert N % NODE_BLOCK == 0, N
    M = cand.shape[0]
    scal = lambda v, dt: jnp.asarray([v], dt)
    return pl.pallas_call(
        _kernel,
        grid=(N // NODE_BLOCK,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # alpha
            pl.BlockSpec(memory_space=pltpu.SMEM),  # beta
            pl.BlockSpec(memory_space=pltpu.SMEM),  # tau
            pl.BlockSpec((NODE_BLOCK, K), lambda i: (i, 0)),
            pl.BlockSpec((NODE_BLOCK, K), lambda i: (i, 0)),
            pl.BlockSpec((M, K), lambda i: (0, 0)),
            pl.BlockSpec((NODE_BLOCK, K), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((NODE_BLOCK, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, K), jnp.float32),
        interpret=interpret,
    )(scal(alpha, jnp.float32), scal(beta, jnp.float32), scal(tau, jnp.float32), pi, mask.astype(jnp.float32), cand, reward_sums)
