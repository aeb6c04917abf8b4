"""Blockwise ring-attention step kernel (Pallas, TPU target).

One *ring step* of blockwise ring attention: the local Q shard attends over
the KV shard currently in flight on the ring, folding the result into the
online-softmax carry ``(m, l, acc)`` that travels across ring steps.  The
surrounding rotate-while-compute schedule (``kernels/ring_attention/ops.py``
over :func:`repro.core.overlap.ring_rotate_compute`) issues the next
``cart_shift(+1)`` collective-permute while this kernel runs.

Differences from the single-device flash kernel (``flash_attention/kernel``):

* the carry is a kernel *input and output* instead of scratch — VMEM scratch
  dies with the ``pallas_call``, but ring state must survive N invocations
  interleaved with permutes;
* Q and K global positions are **traced scalars** (SMEM block): inside
  ``shard_map`` the step's source rank is ``(idx - step) mod n`` with
  ``idx = lax.axis_index``, so block offsets for causal masking cannot be
  Python ints — they ride in through a tiny ``(3,)`` int32 SMEM buffer
  (q_offset, k_offset, kv_len);
* ``kv_len`` masks the ragged tail of an uneven shard (global sequence
  padded to ``n × shard``; padding lives at the tail of the last shards) —
  masked columns never enter the online softmax;
* no finalize: normalisation by ``l`` happens once, after the last ring
  step, in the ops layer.

The carry uses the flash state convention throughout: ``m``/``l``
``(b, h, sq, 1)`` fp32, ``acc`` ``(b, h, sq, d)`` fp32 *unnormalised*.
Masking uses the finite ``NEG_INF`` convention of the flash kernel: a tile
that is entirely masked adds ``exp(0)`` rows that the next real tile's
correction factor ``exp(m_prev - m_new)`` zeroes out, and rows that stay
fully masked across every step resolve to the same uniform softmax as the
reference oracle.
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


def _step_kernel(
    info_ref,      # SMEM (3,) int32: q_offset, k_offset, kv_len
    q_ref,
    k_ref,
    v_ref,
    m_in_ref,
    l_in_ref,
    acc_in_ref,
    m_out_ref,
    l_out_ref,
    acc_out_ref,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_off = info_ref[0]
    k_off = info_ref[1]
    kv_len = info_ref[2]

    # the carry enters through the output refs: loaded once at ki == 0, then
    # accumulated in place across the sequential K walk (out blocks persist
    # while their index map ignores ki)
    @pl.when(ki == 0)
    def _load_carry():
        m_out_ref[...] = m_in_ref[...]
        l_out_ref[...] = l_in_ref[...]
        acc_out_ref[...] = acc_in_ref[...]

    q_start = qi * block_q
    k_start = ki * block_k

    # skip tiles with no unmasked column: the ragged tail beyond kv_len,
    # and (causal) tiles strictly in this Q block's future
    needed = k_start < kv_len
    if causal:
        needed = jnp.logical_and(
            needed, k_off + k_start <= q_off + q_start + block_q - 1
        )

    @pl.when(needed)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                       # (block_q, block_k)

        k_local = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_local < kv_len
        if causal:
            q_pos = q_off + q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_off + k_local
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_out_ref[0, 0]                        # (block_q, 1)
        l_prev = l_out_ref[0, 0]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # (block_q, block_k)
        corr = jnp.exp(m_prev - m_new)                  # (block_q, 1)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        vv = v_ref[0, 0].astype(jnp.float32)            # (block_k, d)
        pv = jax.lax.dot_general(
            p, vv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_out_ref[0, 0] = acc_out_ref[0, 0] * corr + pv
        m_out_ref[0, 0] = m_new
        l_out_ref[0, 0] = l_new


def ring_step_fwd(
    q: jax.Array,        # (b, h, sq, d)  — local Q shard, head-major layout
    k: jax.Array,        # (b, hk, sk, d) — KV shard in flight
    v: jax.Array,        # (b, hk, sk, d)
    m: jax.Array,        # (b, h, sq, 1) fp32 carry
    l: jax.Array,        # (b, h, sq, 1) fp32 carry
    acc: jax.Array,      # (b, h, sq, d) fp32 carry (unnormalised)
    *,
    q_offset: jax.Array,  # () int32, traced — global start of the Q shard
    k_offset: jax.Array,  # () int32, traced — global start of the KV shard
    kv_len: jax.Array,    # () int32, traced — valid rows of the KV shard
    scale: float | None = None,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One ring step: fold ``softmax(q @ k.T) @ v`` of this KV block into
    the carry.  Returns the updated ``(m, l, acc)``.

    Sequence lengths must already be block multiples (the ops layer pads
    once, outside the ring loop; ``kv_len`` masks the padded tail).
    ``interpret=True`` runs the kernel body in Python (CPU validation only;
    refused on a TPU backend).
    """

    b, h, sq, d = q.shape
    _, hk, sk, _ = k.shape
    assert h % hk == 0, (h, hk)
    group = h // hk
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, block_q, sk, block_k)
    nq, nk = sq // block_q, sk // block_k

    info = jnp.stack(
        [
            jnp.asarray(q_offset, jnp.int32),
            jnp.asarray(k_offset, jnp.int32),
            jnp.asarray(kv_len, jnp.int32),
        ]
    )

    kernel = functools.partial(
        _step_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
    )
    carry_q = pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    carry_d = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)
            ),
            carry_q,
            carry_q,
            carry_d,
        ],
        out_specs=[carry_q, carry_q, carry_d],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
        ],
        interpret=checked_interpret(interpret),
    )(info, q, k, v, m, l, acc)


def ring_step_ref(
    q, k, v, m, l, acc, *, q_offset, k_offset, kv_len, scale, causal
):
    """jnp twin of :func:`ring_step_fwd` (same layouts, same masking
    convention) — the XLA-path implementation and the differentiable
    recompute target of the ops-layer backward pass."""

    qf = q.astype(jnp.float32)
    h, hk = q.shape[1], k.shape[1]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if hk != h:
        rep = h // hk
        kf = jnp.repeat(kf, rep, axis=1)
        vf = jnp.repeat(vf, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    sk = s.shape[-1]
    k_local = jnp.arange(sk)[None, :]
    mask = k_local < kv_len
    if causal:
        q_pos = q_offset + jnp.arange(s.shape[-2])[:, None]
        k_pos = k_offset + k_local
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    s = jnp.where(mask[None, None], s, NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return m_new, l_new, acc_new
