"""Per-architecture smoke tests (reduced same-family configs, one forward +
train step on CPU, shape and NaN checks) and decode-vs-prefill consistency:
token-by-token decoding must reproduce the full-sequence forward."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.models import api


def _batch_for(cfg, B=2, S=16, key=0):
    rng = np.random.default_rng(key)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
    }
    if cfg.family == "vlm":
        batch["image_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.num_image_tokens, 1152)), jnp.bfloat16
        )
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, 8, cfg.d_model)), jnp.bfloat16
        )
    return batch


@pytest.mark.parametrize("arch", base.ARCHITECTURES)
def test_smoke_forward_and_train_step(arch):
    cfg = base.get_smoke_config(arch)
    pcfg = base.get_parallel(arch)
    bundle = api.build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg)

    loss, metrics = bundle.loss(params, batch, pcfg, None)
    assert loss.shape == ()
    assert not bool(jnp.isnan(loss)), f"{arch}: NaN loss"
    assert float(loss) > 0

    # one full SGD-ish step: grads exist and are finite for every leaf
    grads = jax.grad(lambda p: bundle.loss(p, batch, pcfg, None)[0])(params)
    flat = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g in flat), arch
    # parameters actually receive gradient signal somewhere
    total = sum(float(jnp.abs(g.astype(jnp.float32)).sum()) for g in flat)
    assert total > 0, f"{arch}: zero gradient"


@pytest.mark.parametrize("arch", base.ARCHITECTURES)
def test_full_config_instantiates(arch):
    cfg = base.get_config(arch)
    assert cfg.param_count() > 1e9 or arch == "seamless_m4t_large_v2"
    assert cfg.padded_vocab % 256 == 0
    shapes = [base.SHAPES[s] for s in base.SHAPES]
    applicable = [s for s in shapes if base.shape_applicable(cfg, s)[0]]
    assert applicable, arch


@pytest.mark.parametrize("arch", [
    "phi4_mini_3_8b",        # dense GQA
    "gemma2_9b",             # local/global + softcaps + post-norms
    "deepseek_v2_236b",      # MLA + MoE
    "grok_1_314b",           # MoE + softcaps
    "mamba2_2_7b",           # SSD
    "zamba2_7b",             # hybrid
    "paligemma_3b",          # VLM prefix-LM
])
def test_decode_matches_prefill(arch):
    """Prefill over S tokens (with one slot of decode headroom), then decode
    token S+1 == prefill of S+1 tokens (the cache is exact, not
    approximate).  Run in float32 so the comparison is tight."""

    import dataclasses

    # float32 + dropless MoE capacity so both paths route identically
    cfg = dataclasses.replace(
        base.get_smoke_config(arch), dtype="float32", capacity_factor=8.0
    )
    pcfg = base.get_parallel(arch)
    bundle = api.build(cfg)
    params = bundle.init(jax.random.PRNGKey(1))
    B, S = 2, 12
    batch = _batch_for(cfg, B=B, S=S + 1, key=7)
    if cfg.family == "vlm":
        batch["image_embeds"] = batch["image_embeds"].astype(jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = batch["frames"].astype(jnp.float32)
    tokens = batch["tokens"]

    pre_batch = {k: (v[:, :S] if k in ("tokens", "labels") else v) for k, v in batch.items()}
    logits_p, cache = bundle.prefill(params, pre_batch, pcfg, None, extra_capacity=1)
    logits_d, _ = bundle.decode(params, cache, tokens[:, S:S + 1], pcfg, None)

    # compare decode at position S against prefill of S+1 tokens
    logits_p2, _ = bundle.prefill(params, batch, pcfg, None)
    np.testing.assert_allclose(
        np.asarray(logits_d, np.float32),
        np.asarray(logits_p2, np.float32),
        atol=2e-3, rtol=2e-3,
    )


def test_gemma2_softcap_and_window_applied():
    cfg = base.get_smoke_config("gemma2_9b")
    pcfg = base.get_parallel("gemma2_9b")
    bundle = api.build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, B=1, S=32)
    logits, _ = bundle.prefill(params, {"tokens": batch["tokens"]}, pcfg, None)
    assert float(jnp.abs(logits).max()) <= cfg.final_logit_softcap + 1e-3


def test_moe_router_balance_metrics():
    cfg = base.get_smoke_config("grok_1_314b")
    pcfg = base.get_parallel("grok_1_314b")
    bundle = api.build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    _, metrics = bundle.loss(params, _batch_for(cfg), pcfg, None)
    assert "load_balance_loss" in metrics
    assert float(metrics["load_balance_loss"]) >= 0.0
    assert 0.0 <= float(metrics["dropped_fraction"]) <= 1.0


def test_param_count_analytic_close_to_actual():
    for arch in base.ARCHITECTURES:
        cfg = base.get_smoke_config(arch)
        bundle = api.build(cfg)
        params = bundle.init(jax.random.PRNGKey(0))
        actual = sum(int(np.prod(np.shape(p))) for p in jax.tree.leaves(params))
        analytic = cfg.param_count()
        assert abs(actual - analytic) / actual < 0.12, (arch, actual, analytic)


# -- decode attention grouped by KV head ---------------------------------------------


def _repeated_attend(q, kc, vc, valid, cfg):
    """The GQA decode formula with the cache repeated to the query heads and
    upcast whole, contracted in fp32: fp32 out, before any cast."""

    from repro.kernels.flash_attention import ref as fa_ref
    from repro.models import attention, common

    h, hk = q.shape[2], kc.shape[2]
    kc = jnp.repeat(kc, h // hk, axis=2).astype(jnp.float32)
    vc = jnp.repeat(vc, h // hk, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kc) * attention._scale(cfg)
    s = common.softcap(s, cfg.attn_logit_softcap)
    mask = valid[None, None, None, :] if valid.ndim == 1 else valid[:, None, None, :]
    s = jnp.where(mask, s, fa_ref.NEG_INF)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vc)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("softcap", [None, 30.0])
@pytest.mark.parametrize("mask", ["shared", "per_row"])
@pytest.mark.parametrize("heads", [(24, 8), (8, 8), (8, 1)], ids=["gqa", "mha", "mqa"])
def test_grouped_decode_attend_matches_repeat(heads, mask, softcap, dtype):
    """Grouping the query heads by KV head is the repeated-cache formula in
    fp32, for GQA, MHA and MQA, shared and per-row masks (rows of length 1
    among them), with and without softcap, over fp32 and bf16 caches."""

    import types

    from repro.models import attention

    h, hk = heads
    b, s, dh = 4, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    # scores of tens, so that a softcap of 30 bends them
    q = (20.0 * jax.random.normal(ks[0], (b, 1, h, dh))).astype(dtype)
    kc = jax.random.normal(ks[1], (b, s, hk, dh)).astype(dtype)
    vc = jax.random.normal(ks[2], (b, s, hk, dh)).astype(dtype)
    if mask == "shared":
        valid = jnp.arange(s) <= 9
    else:
        valid = jnp.arange(s)[None, :] < jnp.array([1, s, 7, 1])[:, None]
    cfg = types.SimpleNamespace(query_scale=None, head_dim=dh, attn_logit_softcap=softcap)

    got = attention._decode_attend(q, kc, vc, valid, cfg)
    want = _repeated_attend(q, kc, vc, valid, cfg)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def _eqn_sizes(jaxpr):
    """Element counts of every equation's outputs, nested jaxprs included."""

    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield int(np.prod(v.aval.shape)), str(eqn.primitive)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqn_sizes(inner)


@pytest.mark.parametrize("per_row", [False, True], ids=["shared_pos", "per_row_pos"])
def test_decode_attention_never_repeats_the_cache(per_row):
    """One GQA decode layer (6 query heads over 2 KV heads), traced: no
    equation outputs an array as large as the cache repeated to the query
    heads, B·S·H·Dh elements (three times the cache)."""

    import dataclasses

    from repro.models import attention

    cfg = dataclasses.replace(
        base.get_smoke_config("phi4_mini_3_8b"), num_heads=6, num_kv_heads=2
    )
    pcfg = base.get_parallel("phi4_mini_3_8b")
    b, s, h, hk, dh = 2, 64, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = attention.init_attention(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    x1 = jnp.zeros((b, 1, cfg.d_model), jnp.bfloat16)
    k_layer = jnp.zeros((b, s, hk, dh), jnp.bfloat16)
    pos = jnp.array([5, 40], jnp.int32) if per_row else jnp.int32(5)

    def layer(p, x1, k_layer, v_layer, pos):
        return attention.attention_decode(
            p, x1, k_layer, v_layer, None, None, pos, cfg, pcfg, sliding_window=None
        )

    jaxpr = jax.make_jaxpr(layer)(p, x1, k_layer, k_layer, pos)
    repeated = b * s * h * dh
    big = [(n, prim) for n, prim in _eqn_sizes(jaxpr.jaxpr) if n >= repeated]
    assert not big, f"outputs of {repeated}+ elements: {big}"
