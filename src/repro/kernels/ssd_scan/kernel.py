"""Pallas kernel for the Mamba-2 SSD chunked scan (TPU target).

TPU adaptation of the SSD algorithm:

* grid ``(batch, heads, num_chunks)`` — chunks are the minor (sequential)
  grid dimension, so the ``(p, n)`` fp32 state lives in VMEM scratch and
  carries across chunk steps (the inter-chunk recurrence), re-initialised
  at ``chunk == 0``;
* each chunk step is three MXU matmuls (``C Bᵀ``, ``(CB ⊙ L) X``,
  ``Xᵀ_w B``) plus VPU elementwise decay math — the "duality" that makes
  SSM training MXU-bound instead of scan-bound;
* ``BlockSpec`` tiles over head-major operands: x/y ``(chunk, p)``, B/C
  ``(chunk, n)`` with the group index derived from the head grid index
  (grouped B/C need no materialised repeat), dt and the within-chunk
  cumsum of ``dt·A`` each as a ``(chunk, 1)`` column and a ``(1, chunk)``
  row;
* default ``chunk=128`` keeps every matmul MXU-aligned and the working set
  (≈ 4·chunk·max(p,n) fp32) far below VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import checked_interpret


def _ssd_kernel(
    x_ref,       # (1, 1, q, p)
    dtc_ref,     # (1, 1, q, 1)   dt as a column
    dtr_ref,     # (1, 1, 1, q)   dt as a row
    cumc_ref,    # (1, 1, q, 1)   within-chunk inclusive cumsum of dt·A, column
    cumr_ref,    # (1, 1, 1, q)   the same as a row
    b_ref,       # (1, 1, q, n)
    c_ref,       # (1, 1, q, n)
    y_ref,       # (1, 1, q, p)
    state_ref,   # VMEM (p, n) fp32 carry
    *,
    chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)            # (q, p)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)     # (q, 1)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)     # (1, q)
    cum_col = cumc_ref[0, 0]                        # (q, 1)
    cum_row = cumr_ref[0, 0]                        # (1, q)
    total = cum_row[:, chunk - 1:]                  # (1, 1)
    B = b_ref[0, 0].astype(jnp.float32)            # (q, n)
    C = c_ref[0, 0].astype(jnp.float32)            # (q, n)

    def mm(lhs, rhs, contract):
        return jax.lax.dot_general(
            lhs, rhs, (contract, ((), ())), preferred_element_type=jnp.float32
        )

    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = qi >= kj

    # intra-chunk: (C Bᵀ ⊙ L) X.  Mask the exponent (not the exp result):
    # above the diagonal cum_i-cum_j is positive and exp() overflows, which
    # would poison autodiff through the kernel with inf·0 (same fix as
    # ref.ssd_chunked).
    cb = mm(C, B, ((1,), (1,)))                     # (q, q)
    seg = jnp.where(lower, cum_col - cum_row, -jnp.inf)
    L = jnp.exp(seg) * dt_row
    y_intra = mm(cb * L, x, ((1,), (0,)))           # (q, p)

    # inter-chunk: exp(cum_i) * (H_in C_i)
    h_in = state_ref[...]                           # (p, n)
    y_inter = jnp.exp(cum_col) * mm(C, h_in, ((1,), (1,)))  # (q, p)

    # state update: H = exp(total) H_in + Xᵀ_w B
    xw = x * (jnp.exp(total - cum_col) * dt_col)    # (q, p)
    state_ref[...] = jnp.exp(total) * h_in + mm(xw, B, ((0,), (0,)))

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)


def ssd_scan_fwd(
    x: jax.Array,    # (b, l, h, p)
    dt: jax.Array,   # (b, l, h)
    A: jax.Array,    # (h,)
    B: jax.Array,    # (b, l, g, n)
    C: jax.Array,    # (b, l, g, n)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Chunked SSD scan; returns y (b, l, h, p).  Zero initial state (the
    training/prefill case; decoding uses the explicit-state step in ref).

    The operands go head-major once, outside the kernel, so every block's
    two minor dims are ``(chunk, p|n|1)`` or ``(1, chunk)`` — the tiling
    Mosaic accepts.  ``interpret=True`` runs the Pallas interpreter (CPU
    validation only; refused on a TPU backend)."""

    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk
    group = h // g

    xt = x.transpose(0, 2, 1, 3)                    # (b, h, l, p)
    dtt = dt.transpose(0, 2, 1)                     # (b, h, l)
    # Mosaic has no cumsum: XLA takes the within-chunk cumsum of dt·A here,
    # in fp32 as the reference does, and the kernel reads it in both
    # orientations (every value stays 2-D, so nothing is transposed inside)
    dA = dtt.astype(jnp.float32) * A.astype(jnp.float32)[None, :, None]
    cum = jnp.cumsum(dA.reshape(b, h, nc, chunk), axis=-1).reshape(b, h, l)
    Bt = B.transpose(0, 2, 1, 3)                    # (b, g, l, n)
    Ct = C.transpose(0, 2, 1, 3)

    def head(bi, hi, ci):
        return (bi, hi, ci, 0)

    def row(bi, hi, ci):
        return (bi, hi, 0, ci)

    def grouped(bi, hi, ci, gg=group):
        return (bi, hi // gg, ci, 0)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), head),
            pl.BlockSpec((1, 1, chunk, 1), head),
            pl.BlockSpec((1, 1, 1, chunk), row),
            pl.BlockSpec((1, 1, chunk, 1), head),
            pl.BlockSpec((1, 1, 1, chunk), row),
            pl.BlockSpec((1, 1, chunk, n), grouped),
            pl.BlockSpec((1, 1, chunk, n), grouped),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p), head),
        out_shape=jax.ShapeDtypeStruct((b, h, l, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=checked_interpret(interpret),
    )(
        xt,
        dtt[..., None], dtt[:, :, None, :],
        cum[..., None], cum[:, :, None, :],
        Bt, Ct,
    )
    return y.transpose(0, 2, 1, 3)
