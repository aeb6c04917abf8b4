"""Every Pallas kernel compiles for a described TPU v5e at real widths.

Nothing runs: the TPU compiler lowers each kernel for a chip that is
described, not attached, and the compiled module must hold the Mosaic
kernel (``tpu_custom_call``).  This catches what interpret mode cannot —
block shapes the chip's tiling refuses, VMEM overuse — at no chip time.
Widths: phi4-mini (4k tokens, 24 query / 8 KV heads of 128) for attention,
mamba2-2.7b (80 heads of 64, state 128, chunk 128, 4k tokens) for the SSD
scan, one phi4 MLP weight (3072 x 8192) for the int8 rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.core.compress import BLOCK
from repro.kernels.flash_attention import kernel as fk
from repro.kernels.quant import kernel as qk
from repro.kernels.ring_attention import kernel as rk
from repro.kernels.ssd_scan import kernel as sk

SEQ, HEADS, KV_HEADS, HEAD_DIM = 4096, 24, 8, 128


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e 2x2, with the persistent compile cache
    off: a compile for a described chip is written but can never be read
    back here, and the next one would warn."""

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # lint: allow-broad-except — skip reason
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _hlo(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_forward_compiles_at_phi4_widths(one_chip):
    bf16 = jnp.bfloat16
    text = _hlo(
        lambda q, k, v: fk.flash_attention_fwd(q, k, v, causal=True),
        [((1, SEQ, HEADS, HEAD_DIM), bf16),
         ((1, SEQ, KV_HEADS, HEAD_DIM), bf16),
         ((1, SEQ, KV_HEADS, HEAD_DIM), bf16)],
        one_chip,
    )
    assert "tpu_custom_call" in text


def test_ring_step_compiles_at_phi4_widths(one_chip):
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32

    def step(q, k, v, m, l, acc, q_off, k_off, kv_len):
        return rk.ring_step_fwd(
            q, k, v, m, l, acc, q_offset=q_off, k_offset=k_off, kv_len=kv_len
        )

    text = _hlo(
        step,
        [((1, HEADS, SEQ, HEAD_DIM), bf16),
         ((1, KV_HEADS, SEQ, HEAD_DIM), bf16),
         ((1, KV_HEADS, SEQ, HEAD_DIM), bf16),
         ((1, HEADS, SEQ, 1), f32),
         ((1, HEADS, SEQ, 1), f32),
         ((1, HEADS, SEQ, HEAD_DIM), f32),
         ((), i32), ((), i32), ((), i32)],
        one_chip,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("direction", ["quantize", "dequantize"])
def test_int8_rows_compile(one_chip, direction):
    rows = 3072 * 8192 // BLOCK
    if direction == "quantize":
        fn = qk.quantize_int8_rows
        shapes = [((rows, BLOCK), jnp.float32)]
    else:
        def fn(q, s):
            return qk.dequantize_int8_rows(q, s, out_dtype=jnp.bfloat16)

        shapes = [((rows, BLOCK), jnp.int8), ((rows, 1), jnp.float32)]
    assert "tpu_custom_call" in _hlo(fn, shapes, one_chip)


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    f32 = jnp.float32
    b, l, h, p, g, n = 1, 4096, 80, 64, 1, 128
    text = _hlo(
        lambda x, dt, A, B, C: sk.ssd_scan_fwd(x, dt, A, B, C, chunk=128),
        [((b, l, h, p), jnp.bfloat16),
         ((b, l, h), f32),
         ((h,), f32),
         ((b, l, g, n), jnp.bfloat16),
         ((b, l, g, n), jnp.bfloat16)],
        one_chip,
    )
    assert "tpu_custom_call" in text
