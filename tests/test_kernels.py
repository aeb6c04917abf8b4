"""Per-kernel allclose validation against the pure-jnp oracles, swept over
shapes and dtypes (Pallas interpret mode on CPU; TPU is the target)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa
from repro.kernels.quant import ops as qo
from repro.kernels.ssd_scan import ops as so


def _qkv(key, B, S, H, Hk, D, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, S, H, D), dtype)
    k = jax.random.normal(k2, (B, S, Hk, D), dtype)
    v = jax.random.normal(k3, (B, S, Hk, D), dtype)
    return q, k, v


@pytest.mark.parametrize("B,S,H,Hk,D", [
    (1, 128, 4, 4, 32),      # MHA
    (2, 256, 4, 2, 32),      # GQA
    (1, 128, 4, 1, 64),      # MQA
    (1, 512, 2, 2, 16),      # long-ish, small heads
])
def test_flash_attention_shapes(B, S, H, Hk, D):
    q, k, v = _qkv(jax.random.PRNGKey(0), B, S, H, Hk, D, jnp.float32)
    out = fa.flash_attention(q, k, v, causal=True, impl="pallas")
    ref = fa.flash_attention(q, k, v, causal=True, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, tol):
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 128, 4, 2, 32, dtype)
    out = fa.flash_attention(q, k, v, causal=True, impl="pallas")
    ref = fa.flash_attention(q, k, v, causal=True, impl="ref")
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("feature", ["window", "softcap", "prefix", "noncausal"])
def test_flash_attention_features(feature):
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 256, 4, 2, 32, jnp.float32)
    kw = dict(causal=True)
    if feature == "window":
        kw["sliding_window"] = 64
    elif feature == "softcap":
        kw["logit_softcap"] = 50.0
    elif feature == "prefix":
        kw["prefix_len"] = 32     # paligemma prefix-LM mask
    elif feature == "noncausal":
        kw["causal"] = False
    out = fa.flash_attention(q, k, v, impl="pallas", **kw)
    ref = fa.flash_attention(q, k, v, impl="ref", **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sq,sk,causal", [
    (100, 100, True),       # below one block: block shrinks, no padding
    (600, 600, True),       # above the default block: padded ragged tail
    (600, 600, False),
    (37, 81, False),        # cross lengths, both ragged
    (130, 50, False),
])
def test_flash_attention_ragged_lengths(sq, sk, causal):
    """Sequence lengths that do not divide the block size: the padded tail
    must be masked out of the online softmax, not averaged in."""

    from repro.kernels.flash_attention import kernel as fk

    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (1, sq, 4, 16))
    k = jax.random.normal(ks[1], (1, sk, 2, 16))
    v = jax.random.normal(ks[2], (1, sk, 2, 16))
    out = fk.flash_attention_fwd(
        q, k, v, causal=causal, block_q=128, block_k=128, interpret=True
    )
    ref = fa.flash_attention(q, k, v, causal=causal, impl="ref")
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("feature", ["window", "prefix", "softcap"])
def test_flash_attention_ragged_features(feature):
    """Ragged tails compose with the masking features: the kv_len mask is
    applied last, so window/prefix logic cannot re-admit padded columns."""

    from repro.kernels.flash_attention import kernel as fk

    S = 330
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, S, 4, 16))
    k = jax.random.normal(ks[1], (1, S, 2, 16))
    v = jax.random.normal(ks[2], (1, S, 2, 16))
    kw = {"window": dict(sliding_window=100),
          "prefix": dict(prefix_len=40),
          "softcap": dict(logit_softcap=30.0)}[feature]
    out = fk.flash_attention_fwd(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True, **kw
    )
    ref = fa.flash_attention(q, k, v, causal=True, impl="ref", **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_grads_match_reference():
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 128, 2, 2, 16, jnp.float32)

    def loss(impl):
        return lambda q, k, v: fa.flash_attention(q, k, v, causal=True, impl=impl).sum()

    g_pal = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss("ref"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# SSD (mamba-2) chunked scan
# ---------------------------------------------------------------------------


def _ssd_inputs(key, B, S, H, P, N, groups=1):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, groups, N))
    C = jax.random.normal(ks[4], (B, S, groups, N))
    return x, dt, A, Bm, C


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 256, 4, 32, 16, 128),
    (2, 128, 2, 16, 32, 64),
    (1, 384, 8, 64, 16, 128),   # S not a multiple of 256
])
def test_ssd_scan_shapes(B, S, H, P, N, chunk):
    x, dt, A, Bm, C = _ssd_inputs(jax.random.PRNGKey(0), B, S, H, P, N)
    out = so.ssd_scan(x, dt, A, Bm, C, chunk=chunk, impl="pallas")
    ref = so.ssd_scan(x, dt, A, Bm, C, chunk=chunk, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5, rtol=5e-5)


def test_ssd_scan_matches_sequential_recurrence():
    """The chunked SSD form must equal the naive per-step SSM recurrence."""

    B, S, H, P, N = 1, 64, 2, 8, 4
    x, dt, A, Bm, C = _ssd_inputs(jax.random.PRNGKey(1), B, S, H, P, N)
    out = so.ssd_scan(x, dt, A, Bm, C, chunk=16, impl="ref")

    state = jnp.zeros((B, H, P, N))
    outs = []
    for t in range(S):
        y, state = so.ssd_decode_step(
            state, x[:, t], dt[:, t], A, Bm[:, t], C[:, t]
        )
        outs.append(y)
    seq = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(seq), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# int8 block quantization
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [256, 1000, 4096])
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_quant_roundtrip(n, impl):
    x = jax.random.normal(jax.random.PRNGKey(0), (n,)) * 3.0
    q, scale, pad = qo.quantize_int8(x, impl=impl)
    assert q.dtype == jnp.int8
    y = qo.dequantize_int8(q, scale, pad, (n,), jnp.float32, impl=impl)
    # per-block absmax int8: error bounded by scale/2 per element
    err = np.abs(np.asarray(y) - np.asarray(x))
    bound = np.abs(np.asarray(x)).max() / 127.0
    assert err.max() <= bound + 1e-6


def test_quant_pallas_matches_ref_exactly():
    x = jax.random.normal(jax.random.PRNGKey(1), (2048,))
    q1, s1, p1 = qo.quantize_int8(x, impl="ref")
    q2, s2, p2 = qo.quantize_int8(x, impl="pallas")
    assert p1 == p2
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_interpret_mode_is_refused_on_a_tpu_backend(monkeypatch):
    """Interpret mode is a CPU-validation choice: on a TPU backend it would
    stand in for the compiled kernel, so asking for it there is an error."""

    from repro.core import errors
    from repro.kernels import checked_interpret

    assert checked_interpret(True) is True   # the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert checked_interpret(False) is False
    q, k, v = _qkv(jax.random.PRNGKey(0), 1, 128, 2, 2, 16, jnp.float32)
    with pytest.raises(errors.UnsupportedError, match="pallas_tpu"):
        fa.flash_attention(q, k, v, causal=True, impl="pallas")
