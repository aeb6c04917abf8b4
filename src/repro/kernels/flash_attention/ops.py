"""Jit'd public wrapper for flash attention.

``flash_attention`` dispatches between the Pallas kernel (TPU target;
``interpret=True`` validation on CPU) and the jnp reference, and installs a
``custom_vjp`` whose backward pass recomputes through the reference — the
standard recompute-backward for memory-bound attention (no O(S²) residuals).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as _kernel
from repro.kernels.flash_attention import ref as _ref


@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(3, 4, 5, 6, 7, 8),
)
def _flash(q, k, v, causal, sliding_window, prefix_len, logit_softcap, scale, impl):
    return _forward(q, k, v, causal, sliding_window, prefix_len, logit_softcap, scale, impl)


def _forward(q, k, v, causal, sliding_window, prefix_len, logit_softcap, scale, impl):
    if impl in ("pallas", "pallas_tpu"):
        return _kernel.flash_attention_fwd(
            q,
            k,
            v,
            causal=causal,
            sliding_window=sliding_window,
            prefix_len=prefix_len,
            logit_softcap=logit_softcap,
            scale=scale,
            interpret=impl == "pallas",
        )
    return _ref.mha(
        q,
        k,
        v,
        causal=causal,
        sliding_window=sliding_window,
        prefix_len=prefix_len,
        logit_softcap=logit_softcap,
        scale=scale,
    )


def _fwd(q, k, v, causal, sliding_window, prefix_len, logit_softcap, scale, impl):
    out = _forward(q, k, v, causal, sliding_window, prefix_len, logit_softcap, scale, impl)
    return out, (q, k, v)


def _bwd(causal, sliding_window, prefix_len, logit_softcap, scale, impl, res, g):
    q, k, v = res

    def recompute(q, k, v):
        return _ref.mha(
            q,
            k,
            v,
            causal=causal,
            sliding_window=sliding_window,
            prefix_len=prefix_len,
            logit_softcap=logit_softcap,
            scale=scale,
        )

    _, vjp = jax.vjp(recompute, q, k, v)
    return vjp(g)


_flash.defvjp(_fwd, _bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: int | None = None,
    prefix_len: int | None = None,
    logit_softcap: float | None = None,
    scale: float | None = None,
    impl: str = "ref",
    q_block_axis: str | None = None,
) -> jax.Array:
    """Public API.  ``impl``:
    'ref'      — O(S²) pure jnp (small shapes, oracle);
    'chunked'  — online-softmax jnp, O(S·block) memory (production XLA path,
                 differentiated directly: the scan already avoids S² residuals);
    'pallas'   — interpret-mode kernel (CPU validation; an error on TPU);
    'pallas_tpu' — the TPU kernel."""

    if impl == "chunked":
        return _ref.chunked_mha(
            q, k, v, causal=causal, sliding_window=sliding_window,
            prefix_len=prefix_len, logit_softcap=logit_softcap, scale=scale,
            q_block_axis=q_block_axis,
        )
    return _flash(q, k, v, causal, sliding_window, prefix_len, logit_softcap, scale, impl)
