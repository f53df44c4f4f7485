"""Pallas TPU kernel: fused SGD + FedProx proximal + weight-decay update.

w' = w - lr * (g + mu*(w - w_global) + wd*w) — the FedProx [56] client
update the paper exposes through the Aggregate hook.  Fusing keeps each
parameter tile resident in VMEM for one read-modify-write instead of
three elementwise passes over HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 2048


def _kernel(h_ref, w_ref, g_ref, w0_ref, o_ref):
    lr, mu, wd = h_ref[0], h_ref[1], h_ref[2]
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w0 = w0_ref[...].astype(jnp.float32)
    out = w - lr * (g + mu * (w - w0) + wd * w)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_update(
    w: jax.Array, g: jax.Array, w0: jax.Array, *, lr: float, mu: float, wd: float,
    interpret: bool = False,
) -> jax.Array:
    """w, g, w0: (L,) with L % TILE == 0 (ops.py pads); returns w.dtype."""
    (L,) = w.shape
    assert L % TILE == 0, L
    hyper = jnp.asarray([lr, mu, wd], jnp.float32)
    return pl.pallas_call(
        _kernel,
        grid=(L // TILE,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # (lr, mu, wd) scalars
            pl.BlockSpec((TILE,), lambda i: (i,)),
            pl.BlockSpec((TILE,), lambda i: (i,)),
            pl.BlockSpec((TILE,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((L,), w.dtype),
        interpret=interpret,
    )(hyper, w, g, w0)
