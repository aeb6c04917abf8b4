"""Attention: GQA/MQA (+ RoPE, sliding window, softcap), MLA (deepseek-v2),
KV caches (bf16 / int8, linear or ring-buffer), and the distributed decode
paths (sequence-sharded cache with flash-decoding merge via repro.core).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core import _compat, overlap
from repro.core.communicator import Communicator
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.models import common
from repro.models.common import dense_init, key_iter


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Per-model stacked KV cache.  ``k``/``v``: (L, B, S, Hk, Dh) in
    ``dtype`` (int8 with per-(token, head) ``*_scale`` when quantised).
    ``length``: ring-buffer capacity == S; ``pos``: global position count.
    Sliding-window layers use S == window with ring addressing."""

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array | None
    v_scale: jax.Array | None
    pos: jax.Array  # () int32 — number of tokens already cached

    @staticmethod
    def init(
        num_layers: int,
        batch: int,
        length: int,
        kv_heads: int,
        head_dim: int,
        *,
        dtype=jnp.bfloat16,
        quantized: bool = False,
    ) -> "KVCache":
        shape = (num_layers, batch, length, kv_heads, head_dim)
        if quantized:
            return KVCache(
                k=jnp.zeros(shape, jnp.int8),
                v=jnp.zeros(shape, jnp.int8),
                k_scale=jnp.zeros(shape[:-1] + (1,), jnp.float32),
                v_scale=jnp.zeros(shape[:-1] + (1,), jnp.float32),
                pos=jnp.zeros((), jnp.int32),
            )
        return KVCache(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            k_scale=None,
            v_scale=None,
            pos=jnp.zeros((), jnp.int32),
        )


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(token, head) symmetric int8: x (..., Dh) → (int8, fp32 scale)."""

    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _row_update(layer: jax.Array, new: jax.Array, write_pos: jax.Array) -> jax.Array:
    """Per-row cache write: ``layer`` (B, S, ...), ``new`` (B, T, ...),
    ``write_pos`` (B,) — each batch row writes at its own position (the
    continuous-batching slot table, where rows decode at ragged depths)."""

    return jax.vmap(
        lambda l, n, w: jax.lax.dynamic_update_slice_in_dim(l, n, w, axis=0)
    )(layer, new.astype(layer.dtype), write_pos)


def cache_layer_update(
    k_layer: jax.Array,
    v_layer: jax.Array,
    k_scale_l: jax.Array | None,
    v_scale_l: jax.Array | None,
    k_new: jax.Array,
    v_new: jax.Array,
    pos: jax.Array,
    *,
    ring: bool,
) -> tuple[jax.Array, jax.Array, jax.Array | None, jax.Array | None]:
    """Write k_new/v_new (B, T, Hk, Dh) at ``pos`` (ring: pos % capacity).

    ``pos`` is the shared scalar in the fixed-batch serving path, or a
    per-row ``(B,)`` vector when rows live at different depths (the
    continuous-batching engine); vector positions write through a vmapped
    per-row update."""

    capacity = k_layer.shape[1]
    write_pos = (pos % capacity) if ring else pos
    per_row = jnp.ndim(pos) == 1
    if k_layer.dtype == jnp.int8:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        if per_row:
            k_layer = _row_update(k_layer, kq, write_pos)
            v_layer = _row_update(v_layer, vq, write_pos)
            k_scale_l = _row_update(k_scale_l, ks, write_pos)
            v_scale_l = _row_update(v_scale_l, vs, write_pos)
        else:
            k_layer = jax.lax.dynamic_update_slice_in_dim(k_layer, kq, write_pos, axis=1)
            v_layer = jax.lax.dynamic_update_slice_in_dim(v_layer, vq, write_pos, axis=1)
            k_scale_l = jax.lax.dynamic_update_slice_in_dim(k_scale_l, ks, write_pos, axis=1)
            v_scale_l = jax.lax.dynamic_update_slice_in_dim(v_scale_l, vs, write_pos, axis=1)
    elif per_row:
        k_layer = _row_update(k_layer, k_new, write_pos)
        v_layer = _row_update(v_layer, v_new, write_pos)
    else:
        k_layer = jax.lax.dynamic_update_slice_in_dim(
            k_layer, k_new.astype(k_layer.dtype), write_pos, axis=1
        )
        v_layer = jax.lax.dynamic_update_slice_in_dim(
            v_layer, v_new.astype(v_layer.dtype), write_pos, axis=1
        )
    return k_layer, v_layer, k_scale_l, v_scale_l


def cache_layer_read(k_layer, v_layer, k_scale_l, v_scale_l, dtype):
    if k_layer.dtype == jnp.int8:
        return (
            _dequantize_kv(k_layer, k_scale_l, dtype),
            _dequantize_kv(v_layer, v_scale_l, dtype),
        )
    return k_layer.astype(dtype), v_layer.astype(dtype)


# ---------------------------------------------------------------------------
# GQA parameters
# ---------------------------------------------------------------------------


def init_attention(key, cfg, dtype) -> common.Params:
    d, h, hk, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = key_iter(key)
    p = {
        "wq": dense_init(next(ks), d, (d, h, dh), dtype),
        "wk": dense_init(next(ks), d, (d, hk, dh), dtype),
        "wv": dense_init(next(ks), d, (d, hk, dh), dtype),
        "wo": dense_init(next(ks), h * dh, (h, dh, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh), dtype)
        p["bk"] = jnp.zeros((hk, dh), dtype)
        p["bv"] = jnp.zeros((hk, dh), dtype)
    return p


def _project_qkv(p, x, cfg, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = common.rope(q, positions, theta=cfg.rope_theta)
    k = common.rope(k, positions, theta=cfg.rope_theta)
    return q, k, v


def _scale(cfg) -> float:
    return cfg.query_scale if cfg.query_scale is not None else 1.0 / math.sqrt(cfg.head_dim)


# ---------------------------------------------------------------------------
# full-sequence attention (train / prefill)
# ---------------------------------------------------------------------------


def attention_full(
    p: common.Params,
    x: jax.Array,            # (B, S, D)
    cfg,
    pcfg,
    *,
    positions: jax.Array,    # (S,) or (B, S)
    sliding_window: int | None,
    prefix_len: int | None = None,
    mesh=None,
) -> jax.Array:
    q, k, v = _project_qkv(p, x, cfg, positions)

    if pcfg.ring_attention and mesh is not None and not cfg.attn_logit_softcap and \
            sliding_window is None and prefix_len is None:
        out = _ring_attention_sharded(q, k, v, pcfg, mesh, scale=_scale(cfg))
    else:
        out = fa_ops.flash_attention(
            q,
            k,
            v,
            causal=True,
            sliding_window=sliding_window,
            prefix_len=prefix_len,
            logit_softcap=cfg.attn_logit_softcap,
            scale=_scale(cfg),
            impl=getattr(pcfg, "attn_impl", "ref"),
            q_block_axis=pcfg.model_axis if pcfg.attn_plan == "sp" else None,
        )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def _ring_attention_sharded(q, k, v, pcfg, mesh, *, scale, causal=True):
    """Sequence parallelism for training and long prefill: shard the
    sequence over the model axis, fold it onto a 1-D periodic cart ring and
    run the fused blockwise ring kernel (``kernels/ring_attention``) — the
    stacked KV buffer rotates via ``cart_shift(+1)`` collective-permutes
    hidden behind each step's compute.  Global lengths that do not divide
    the ring are padded here (the kernel masks the tail) and sliced back."""

    from jax.sharding import PartitionSpec as P

    from repro.core import topology
    from repro.kernels.ring_attention import ops as ring_ops

    axis = pcfg.model_axis
    n = mesh.shape[axis]
    cart = topology.CartComm(
        mesh, (axis,), dims=(n,), periods=(True,), managed=False, tag="ring-attn"
    )
    s = q.shape[1]
    pad = (-s) % n
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, widths), jnp.pad(k, widths), jnp.pad(v, widths)
    spec = P(pcfg.data_axes, axis, None, None)
    impl = {"chunked": "ref"}.get(
        getattr(pcfg, "attn_impl", "ref"), getattr(pcfg, "attn_impl", "ref")
    )

    def body(ql, kl, vl):
        return ring_ops.ring_attention(
            cart, ql, kl, vl, causal=causal, scale=scale, global_len=s, impl=impl
        )

    out = _compat.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
    return out[:, :s] if pad else out


# ---------------------------------------------------------------------------
# prefill / decode with cache
# ---------------------------------------------------------------------------


def attention_prefill(
    p, x, cfg, pcfg, *, positions, sliding_window, prefix_len=None, mesh=None
):
    """Full-sequence attention that also returns the layer's new KV entries
    (B, S_cache, Hk, Dh) — S_cache is min(S, window) for windowed layers."""

    q, k, v = _project_qkv(p, x, cfg, positions)
    if pcfg.ring_attention and mesh is not None and not cfg.attn_logit_softcap and \
            sliding_window is None and prefix_len is None:
        # long-prompt prefill: the ring kernel admits prompts whose KV does
        # not fit one device — same sharded-sequence path as training
        out = _ring_attention_sharded(q, k, v, pcfg, mesh, scale=_scale(cfg))
    else:
        out = fa_ops.flash_attention(
            q,
            k,
            v,
            causal=True,
            sliding_window=sliding_window,
            prefix_len=prefix_len,
            logit_softcap=cfg.attn_logit_softcap,
            scale=_scale(cfg),
            impl=getattr(pcfg, "attn_impl", "ref"),
            q_block_axis=pcfg.model_axis if pcfg.attn_plan == "sp" else None,
        )
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if sliding_window is not None and k.shape[1] > sliding_window:
        # ring-buffer layout: slot i holds the latest token with pos%win == i
        s = k.shape[1]
        start = s - sliding_window
        # ring layout: global position p lives in slot p % window
        roll = s % sliding_window
        k_keep = jnp.roll(k[:, start:], roll, axis=1)
        v_keep = jnp.roll(v[:, start:], roll, axis=1)
        return y, (k_keep, v_keep)
    return y, (k, v)


def attention_decode(
    p,
    x1: jax.Array,           # (B, 1, D)
    k_layer,
    v_layer,
    k_scale_l,
    v_scale_l,
    pos: jax.Array,          # () int32 tokens already cached — or (B,) per-row
    cfg,
    pcfg,
    *,
    sliding_window: int | None,
    mesh=None,
):
    """Single-token attention against a cached layer.  Returns
    (y (B,1,D), updated cache slices).  Scalar ``pos`` is the fixed-batch
    path (all rows at one depth); a ``(B,)`` vector gives each row its own
    depth — the per-slot position of the continuous-batching engine — with
    a per-row validity mask replacing the shared one."""

    dtype = x1.dtype
    per_row = jnp.ndim(pos) == 1
    positions = pos[:, None] if per_row else pos[None]
    q, k_new, v_new = _project_qkv(p, x1, cfg, positions)
    ring = sliding_window is not None and k_layer.shape[1] == sliding_window
    k_layer, v_layer, k_scale_l, v_scale_l = cache_layer_update(
        k_layer, v_layer, k_scale_l, v_scale_l, k_new, v_new, pos, ring=ring
    )
    capacity = k_layer.shape[1]

    # pos broadcasts against the slot index: () keeps the shared (capacity,)
    # mask, (B, 1) makes it per-row (B, capacity)
    pos_b = pos[:, None] if per_row else pos
    if ring:
        # slot i holds global position p_i = pos - ((pos - i) mod capacity)
        slots = jnp.arange(capacity)
        slot_pos = pos_b - ((pos_b - slots) % capacity)
        valid = slot_pos >= jnp.maximum(0, pos_b - capacity + 1)
        valid = jnp.logical_and(valid, slot_pos <= pos_b)
    else:
        slot_pos = jnp.arange(capacity)
        valid = slot_pos <= pos_b
    if sliding_window is not None:
        valid = jnp.logical_and(valid, pos_b - slot_pos < sliding_window)

    if (
        pcfg.seq_shard_cache
        and pcfg.flash_decode_merge
        and mesh is not None
        and not ring
    ):
        y = _flash_decode_sharded(
            q, k_layer, v_layer, k_scale_l, v_scale_l, valid, cfg, pcfg, mesh, dtype
        )
    else:
        kc, vc = cache_layer_read(k_layer, v_layer, k_scale_l, v_scale_l, dtype)
        y = _decode_attend(q, kc, vc, valid, cfg).astype(dtype)
    y = jnp.einsum("bshk,hkd->bsd", y, p["wo"])
    return y, (k_layer, v_layer, k_scale_l, v_scale_l)


def _grouped_scores(q, kc, valid, cfg):
    """Masked fp32 scores of decode queries ``q`` (B, T, H, Dh) against a
    cached layer as stored, ``kc`` (B, S, Hk, Dh): (B, Hk, G, T, S) with
    G = H // Hk.  Query head h reads KV head h // G (``jnp.repeat``'s
    order), so the cache is never repeated to the query heads nor copied to
    fp32: it enters the contraction as stored, accumulated in fp32.
    HIGHEST keeps an MXU lowering of the grouped shape exact in fp32."""

    b, t, h, dh = q.shape
    hk = kc.shape[2]
    qg = q.reshape(b, t, hk, h // hk, dh)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, kc,
        preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST,
    )
    s = s * _scale(cfg)
    s = common.softcap(s, cfg.attn_logit_softcap)
    # valid is (S,) shared across the batch, or (B, S) per-row
    mask = valid if valid.ndim == 1 else valid[:, None, None, None, :]
    return jnp.where(mask, s, fa_ref.NEG_INF)


def _grouped_values(pattn, vc):
    """fp32 ``pattn`` (B, Hk, G, T, S) against the cached ``vc``
    (B, S, Hk, Dh) as stored → fp32 (B, T, H, Dh).  On the TPU the einsum's
    promotion of ``vc`` to fp32 fuses into the contraction: no fp32 copy."""

    b, hk, g, t, _ = pattn.shape
    o = jnp.einsum(
        "bhgqk,bkhd->bqhgd", pattn, vc,
        preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST,
    )
    return o.reshape(b, t, hk * g, vc.shape[-1])


def _decode_attend(q, kc, vc, valid, cfg):
    """Decode attention of ``q`` (B, T, H, Dh) over a cached layer; fp32
    (B, T, H, Dh), cast by the caller."""

    pattn = jax.nn.softmax(_grouped_scores(q, kc, valid, cfg), axis=-1)
    return _grouped_values(pattn, vc)


def _flash_decode_sharded(q, k_layer, v_layer, k_scale_l, v_scale_l, valid, cfg, pcfg,
                          mesh, dtype):
    """Sequence-sharded KV cache decode: each model-axis shard attends over
    its slice, then the exact softmax merge combines (O(B·H) payload instead
    of all-gathering the cache)."""

    from jax.sharding import PartitionSpec as P

    axis = pcfg.model_axis
    comm = Communicator(mesh, (axis,))
    b_axes = pcfg.data_axes
    q_spec = P(b_axes, None, None, None)
    kv_spec = P(b_axes, axis, None, None)
    sc_spec = None if k_scale_l is None else P(b_axes, axis, None, None)
    valid_spec = P(axis) if valid.ndim == 1 else P(b_axes, axis)

    def body(ql, kl, vl, ksl, vsl, validl):
        kc, vc = cache_layer_read(kl, vl, ksl, vsl, dtype)
        s = _grouped_scores(ql, kc, validl, cfg)
        m = jnp.max(s, axis=-1)
        p_ = jnp.exp(s - m[..., None])
        l = jnp.sum(p_, axis=-1)
        o = _grouped_values(p_, vc)
        # (B, Hk, G, T) → (B, H, T), the head order of ``o``
        b, hk, g, t = m.shape
        m, l = m.reshape(b, hk * g, t), l.reshape(b, hk * g, t)
        o = o / jnp.maximum(jnp.swapaxes(l, 1, 2), 1e-30)[..., None]
        return overlap.merge_partial_attention(o, m, l, comm).astype(ql.dtype)

    args = [q, k_layer, v_layer]
    specs = [q_spec, kv_spec, kv_spec]
    if k_scale_l is not None:
        body_fn = body
        args += [k_scale_l, v_scale_l, valid]
        specs += [sc_spec, sc_spec, valid_spec]
    else:
        def body_fn(ql, kl, vl, validl):  # type: ignore[misc]
            return body(ql, kl, vl, None, None, validl)

        args += [valid]
        specs += [valid_spec]
    return _compat.shard_map(
        body_fn,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=q_spec,
    )(*args)


# ---------------------------------------------------------------------------
# MLA (deepseek-v2): low-rank Q/KV with compressed cache + absorbed decode
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MLACache:
    """Compressed latent cache: ``ckv`` (L, B, S, kv_lora), ``k_rope``
    (L, B, S, rope_dim), ``pos`` ()."""

    ckv: jax.Array
    k_rope: jax.Array
    pos: jax.Array

    @staticmethod
    def init(num_layers, batch, length, kv_lora, rope_dim, dtype=jnp.bfloat16):
        return MLACache(
            ckv=jnp.zeros((num_layers, batch, length, kv_lora), dtype),
            k_rope=jnp.zeros((num_layers, batch, length, rope_dim), dtype),
            pos=jnp.zeros((), jnp.int32),
        )


def init_mla(key, cfg, dtype) -> common.Params:
    d, h = cfg.d_model, cfg.num_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ks = key_iter(key)
    return {
        "wq_a": dense_init(next(ks), d, (d, cfg.q_lora), dtype),
        "q_norm": common.init_rmsnorm(cfg.q_lora, dtype),
        "wq_b": dense_init(next(ks), cfg.q_lora, (cfg.q_lora, h, dn + dr), dtype),
        "wkv_a": dense_init(next(ks), d, (d, cfg.kv_lora + dr), dtype),
        "kv_norm": common.init_rmsnorm(cfg.kv_lora, dtype),
        "wk_b": dense_init(next(ks), cfg.kv_lora, (cfg.kv_lora, h, dn), dtype),
        "wv_b": dense_init(next(ks), cfg.kv_lora, (cfg.kv_lora, h, dv), dtype),
        "wo": dense_init(next(ks), h * dv, (h, dv, d), dtype),
    }


def _mla_scale(cfg) -> float:
    return 1.0 / math.sqrt(cfg.nope_head_dim + cfg.rope_head_dim)


def _mla_latents(p, x, cfg, positions):
    """Shared q/kv latent computation.  Returns (q_nope, q_rope, ckv, k_rope)."""

    cq = common.rms_norm(jnp.einsum("bsd,dq->bsq", x, p["wq_a"]), p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsq,qhk->bshk", cq, p["wq_b"])
    q_nope, q_rope = q[..., : cfg.nope_head_dim], q[..., cfg.nope_head_dim :]
    q_rope = common.rope(q_rope, positions, theta=cfg.rope_theta)

    kv = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"])
    ckv, k_rope = kv[..., : cfg.kv_lora], kv[..., cfg.kv_lora :]
    ckv = common.rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    k_rope = common.rope(k_rope[:, :, None, :], positions, theta=cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, ckv, k_rope


def mla_attention_full(p, x, cfg, pcfg, *, positions, mesh=None, return_cache=False):
    """Training/prefill MLA: expand the latents and run standard attention."""

    q_nope, q_rope, ckv, k_rope = _mla_latents(p, x, cfg, positions)
    k_nope = jnp.einsum("bsk,khn->bshn", ckv, p["wk_b"])
    v = jnp.einsum("bsk,khv->bshv", ckv, p["wv_b"])
    h = cfg.num_heads
    k_rope_h = jnp.broadcast_to(
        k_rope[:, :, None, :], k_rope.shape[:2] + (h, cfg.rope_head_dim)
    )
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    out = fa_ops.flash_attention(
        q_full,
        k_full,
        v,
        causal=True,
        scale=_mla_scale(cfg),
        impl=getattr(pcfg, "attn_impl", "ref"),
    )
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    if return_cache:
        return y, (ckv, k_rope)
    return y


def mla_attention_decode(p, x1, ckv_layer, krope_layer, pos, cfg, pcfg, *, mesh=None):
    """Absorbed decode: attend in the compressed latent space — the W^UK
    absorption that makes the MLA cache pay off (no per-step expansion)."""

    per_row = jnp.ndim(pos) == 1
    positions = pos[:, None] if per_row else pos[None]
    q_nope, q_rope, ckv_new, krope_new = _mla_latents(p, x1, cfg, positions)
    if per_row:
        ckv_layer = _row_update(ckv_layer, ckv_new, pos)
        krope_layer = _row_update(krope_layer, krope_new, pos)
    else:
        ckv_layer = jax.lax.dynamic_update_slice_in_dim(
            ckv_layer, ckv_new.astype(ckv_layer.dtype), pos, axis=1
        )
        krope_layer = jax.lax.dynamic_update_slice_in_dim(
            krope_layer, krope_new.astype(krope_layer.dtype), pos, axis=1
        )
    capacity = ckv_layer.shape[1]
    pos_b = pos[:, None] if per_row else pos
    valid = jnp.arange(capacity) <= pos_b

    # absorb: q_latent = q_nope @ W^UK  → (B, 1, H, kv_lora)
    q_latent = jnp.einsum("bshn,khn->bshk", q_nope, p["wk_b"])
    s = jnp.einsum(
        "bshk,btk->bhst", q_latent.astype(jnp.float32), ckv_layer.astype(jnp.float32)
    )
    s = s + jnp.einsum(
        "bshr,btr->bhst", q_rope.astype(jnp.float32), krope_layer.astype(jnp.float32)
    )
    s = s * _mla_scale(cfg)
    mask = valid[None, None, None, :] if valid.ndim == 1 else valid[:, None, None, :]
    s = jnp.where(mask, s, fa_ref.NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    o_latent = jnp.einsum("bhst,btk->bshk", pattn, ckv_layer.astype(jnp.float32))
    out = jnp.einsum("bshk,khv->bshv", o_latent.astype(x1.dtype), p["wv_b"])
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    return y, (ckv_layer, krope_layer)
