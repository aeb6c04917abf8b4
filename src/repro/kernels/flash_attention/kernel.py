"""Blockwise flash-attention forward kernel (Pallas, TPU target).

TPU adaptation of the blockwise online-softmax algorithm:

* grid ``(batch, q_heads, num_q_blocks, num_k_blocks)`` — the K axis is the
  minor (sequential) grid dimension, so the VMEM scratch accumulators carry
  across K steps of one (b, h, qi) tile;
* ``BlockSpec`` tiles: Q/O ``(block_q, head_dim)``, K/V ``(block_k,
  head_dim)`` — VMEM working set is ``(2·block_q + 2·block_k) · d`` floats,
  sized well under the ~16 MB VMEM budget for the default 512/512 blocks;
* matmul dims are MXU-aligned: ``block_q``/``block_k`` multiples of 128 and
  ``head_dim`` ∈ {64, 128, 224, 256} pad to lane width internally;
* GQA is free: the K/V ``index_map`` divides the query-head grid index by
  the group size instead of materialising repeated heads;
* causal tiles above the diagonal are skipped with ``pl.when`` (no FLOPs,
  no VMEM traffic), halving causal work;
* optional sliding-window masking and tanh logit soft-capping (gemma-2)
  happen on the fp32 logits tile in registers.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import checked_interpret

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scratch,
    l_scratch,
    acc_scratch,
    *,
    scale: float,
    causal: bool,
    sliding_window: int | None,
    prefix_len: int | None,
    logit_softcap: float | None,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    kv_len: int | None,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q_start = qi * block_q
    k_start = ki * block_k

    # causal skip: tile strictly above the diagonal contributes nothing;
    # with a ragged K length the padded tail tiles are skipped the same way
    needed = True
    if causal:
        needed = k_start <= q_start + block_q - 1
    if kv_len is not None:
        tail_ok = k_start < kv_len
        needed = tail_ok if needed is True else jnp.logical_and(needed, tail_ok)

    @pl.when(needed)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                       # (block_q, block_k)
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask = q_pos >= k_pos
        if sliding_window is not None:
            mask = jnp.logical_and(mask, q_pos - k_pos < sliding_window)
        if prefix_len is not None:
            mask = jnp.logical_or(mask, k_pos < prefix_len)
        if kv_len is not None:
            # ragged tail: padded K columns are masked out of the online
            # softmax (applied last so prefix_len cannot re-admit them)
            mask = jnp.logical_and(mask, k_pos < kv_len)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[...]                         # (block_q, 1)
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # (block_q, block_k)
        corr = jnp.exp(m_prev - m_new)                  # (block_q, 1)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        vv = v_ref[0, 0].astype(jnp.float32)            # (block_k, d)
        pv = jax.lax.dot_general(
            p, vv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scratch[...] = acc_scratch[...] * corr + pv
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scratch[...]
        o_ref[0, 0] = (acc_scratch[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: int | None = None,
    prefix_len: int | None = None,
    logit_softcap: float | None = None,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """q: (b, sq, h, d); k/v: (b, sk, hk, d), h % hk == 0.  → (b, sq, h, d).

    ``interpret=True`` executes the kernel body in Python (CPU validation
    only; refused on a TPU backend).
    """

    b, sq, h, d = q.shape
    _, sk, hk, _ = k.shape
    assert h % hk == 0, (h, hk)
    group = h // hk
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    # ragged sequence lengths: pad up to block multiples.  Padded Q rows are
    # sliced off the output; padded K columns are masked out of the online
    # softmax inside the kernel (kv_len), never averaged in.
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    kv_len = sk if pad_k else None
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sqp, skp = sq + pad_q, sk + pad_k
    nq, nk = sqp // block_q, skp // block_k

    # layout: (b, h, s, d) blocks — heads are a pure grid dimension
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        sliding_window=sliding_window,
        prefix_len=prefix_len,
        logit_softcap=logit_softcap,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
        kv_len=kv_len,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=checked_interpret(interpret),
    )(qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)
    return out[:, :sq] if pad_q else out
