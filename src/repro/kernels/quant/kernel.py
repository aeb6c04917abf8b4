"""Pallas kernel for per-block symmetric int8 quantisation (TPU target).

Used on the gradient-compression path (cross-pod reduction payloads) and for
int8 KV caches.  Layout: the flat payload is reshaped to ``(rows, BLOCK)``;
the grid tiles rows, each tile computing VPU absmax→scale→round entirely in
VMEM.  ``BLOCK = 256`` (two 128-lane vregs) keeps reductions lane-aligned.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.compress import BLOCK
from repro.kernels import checked_interpret

ROW_TILE = 64


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                    # (rows, BLOCK)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def quantize_int8_rows(
    x: jax.Array, *, row_tile: int = ROW_TILE, interpret: bool = False
) -> tuple[jax.Array, jax.Array]:
    """x: (rows, BLOCK) fp — returns (int8 (rows, BLOCK), fp32 scales (rows, 1))."""

    rows, width = x.shape
    assert width == BLOCK, (width, BLOCK)
    row_tile = min(row_tile, rows)
    assert rows % row_tile == 0, (rows, row_tile)
    grid = (rows // row_tile,)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((row_tile, BLOCK), lambda r: (r, 0))],
        out_specs=[
            pl.BlockSpec((row_tile, BLOCK), lambda r: (r, 0)),
            pl.BlockSpec((row_tile, 1), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=checked_interpret(interpret),
    )(x)
    return q, s


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(x_ref.dtype)


def dequantize_int8_rows(
    q: jax.Array, s: jax.Array, *, out_dtype=jnp.float32, row_tile: int = ROW_TILE,
    interpret: bool = False,
) -> jax.Array:
    rows, width = q.shape
    assert width == BLOCK
    row_tile = min(row_tile, rows)
    assert rows % row_tile == 0
    return pl.pallas_call(
        _dequant_kernel,
        grid=(rows // row_tile,),
        in_specs=[
            pl.BlockSpec((row_tile, BLOCK), lambda r: (r, 0)),
            pl.BlockSpec((row_tile, 1), lambda r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, BLOCK), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, BLOCK), out_dtype),
        interpret=checked_interpret(interpret),
    )(q, s)
