"""Pallas TPU kernels: QSGD int8 stochastic quantize / dequantize.

The compression hook on the cross-zone aggregation hop (paper Table II's
custom compression functions; refs [37] QSGD).  Rows of 256 values share
one f32 max-abs scale; stochastic rounding consumes pre-supplied uniform
bits so the kernel is bit-identical to ``ref.quantize_ref`` (and to the
pure-JAX path used inside the train step).

Tiling: (ROWS_PER_BLOCK, 256) blocks in VMEM — the trailing 256 is lane-
aligned; row blocks keep the footprint < 1 MB.  Any row count R runs on
``cdiv(R, ROWS_PER_BLOCK)`` blocks: rows are independent, so the last
block's rows past R only feed outputs that are never written back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW = 256
ROWS_PER_BLOCK = 256
LEVELS = 127


def _quant_kernel(x_ref, r_ref, q_ref, s_ref, *, levels: int = LEVELS):
    x = x_ref[...].astype(jnp.float32)  # (RB, 256)
    r = r_ref[...].astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / levels
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.floor(x / scale + r)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "levels"))
def qsgd_quantize(
    x: jax.Array, rand: jax.Array, *, interpret: bool = False, levels: int = LEVELS
):
    """x, rand: (R, 256), any R >= 1 -> (int8 (R,256), f32 (R,1)).

    ``levels`` (static, <= 127) is the per-sign lattice size — the
    ``CompressionPolicy.levels`` knob; the grid respecializes per value."""
    R, W = x.shape
    assert W == ROW, (R, W)
    grid = (pl.cdiv(R, ROWS_PER_BLOCK),)
    return pl.pallas_call(
        functools.partial(_quant_kernel, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS_PER_BLOCK, ROW), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_BLOCK, ROW), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS_PER_BLOCK, ROW), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_BLOCK, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, ROW), jnp.int8),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, rand)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qsgd_dequantize(q: jax.Array, scale: jax.Array, *, interpret: bool = False) -> jax.Array:
    R, W = q.shape
    assert W == ROW and R % ROWS_PER_BLOCK == 0, (R, W)
    return pl.pallas_call(
        _dequant_kernel,
        grid=(R // ROWS_PER_BLOCK,),
        in_specs=[
            pl.BlockSpec((ROWS_PER_BLOCK, ROW), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_BLOCK, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS_PER_BLOCK, ROW), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, ROW), jnp.float32),
        interpret=interpret,
    )(q, scale)
