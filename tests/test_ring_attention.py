"""Ring attention (``kernels/ring_attention``): the fused cart-ring +
flash-attention path.  Single-device tests exercise the step kernel against
its jnp twin and the n=1 degenerate ring; the shard_map parity tests (even /
uneven global lengths, causal / non-causal, gradients, serving prefill) run
on 8 virtual devices through the ``subproc`` fixture."""

from __future__ import annotations

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa
from repro.kernels.ring_attention import kernel as rk


def _qkv(key, B, S, H, Hk, D):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, H, S, D))      # head-major (kernel layout)
    k = jax.random.normal(k2, (B, Hk, S, D))
    v = jax.random.normal(k3, (B, Hk, S, D))
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("Hk", [4, 2])
def test_ring_step_kernel_matches_jnp_twin(causal, Hk):
    B, S, H, D = 1, 64, 4, 16
    q, k, v = _qkv(jax.random.PRNGKey(0), B, S, H, Hk, D)
    # a mid-schedule carry (not the initial one): m finite, l/acc nonzero
    m = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, 1)) * 0.5
    l = jax.random.uniform(jax.random.PRNGKey(2), (B, H, S, 1)) + 1.0
    acc = jax.random.normal(jax.random.PRNGKey(3), (B, H, S, D))
    kw = dict(
        q_offset=jnp.int32(64), k_offset=jnp.int32(32), kv_len=jnp.int32(50),
        scale=0.25, causal=causal,
    )
    out_k = rk.ring_step_fwd(
        q, k, v, m, l, acc, block_q=32, block_k=32, interpret=True, **kw
    )
    out_r = rk.ring_step_ref(q, k, v, m, l, acc, **kw)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)


def test_ring_step_skips_fully_masked_tiles_consistently():
    """Tiles entirely beyond kv_len or entirely in the causal future must be
    skipped without perturbing the carry (the tile-skip predicate and the
    in-tile mask must agree)."""

    B, S, H, D = 1, 64, 2, 16
    q, k, v = _qkv(jax.random.PRNGKey(4), B, S, H, H, D)
    m = jnp.full((B, H, S, 1), rk.NEG_INF)
    l = jnp.zeros((B, H, S, 1))
    acc = jnp.zeros((B, H, S, D))
    # KV block strictly in the future of every Q row: carry must be unchanged
    kw = dict(q_offset=jnp.int32(0), k_offset=jnp.int32(512),
              kv_len=jnp.int32(64), scale=0.25, causal=True)
    m2, l2, acc2 = rk.ring_step_fwd(
        q, k, v, m, l, acc, block_q=32, block_k=32, interpret=True, **kw
    )
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(m))
    np.testing.assert_array_equal(np.asarray(l2), np.asarray(l))
    np.testing.assert_array_equal(np.asarray(acc2), np.asarray(acc))
    # kv_len == 0 (a fully padded shard): same invariant, non-causal
    kw = dict(q_offset=jnp.int32(0), k_offset=jnp.int32(0),
              kv_len=jnp.int32(0), scale=0.25, causal=False)
    m2, l2, acc2 = rk.ring_step_fwd(
        q, k, v, m, l, acc, block_q=32, block_k=32, interpret=True, **kw
    )
    np.testing.assert_array_equal(np.asarray(l2), np.asarray(l))


@pytest.mark.parametrize("causal", [True, False])
def test_degenerate_ring_of_one_matches_flash(causal):
    """n=1 periodic ring (a single-device mesh): zero permutes, one step —
    must equal the dense flash reference exactly."""

    from repro.core import _compat, topology
    from repro.kernels.ring_attention import ops as ring_ops

    mesh = _compat.make_mesh((1,), ("ring",))
    cart = topology.CartComm(
        mesh, ("ring",), dims=(1,), periods=(True,), managed=False, tag="r1"
    )
    key = jax.random.PRNGKey(5)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (2, 48, 4, 16))
    k = jax.random.normal(k2, (2, 48, 2, 16))
    v = jax.random.normal(k3, (2, 48, 2, 16))
    with mesh:
        out = ring_ops.ring_attention(
            cart, q, k, v, causal=causal, impl="pallas", block_q=32, block_k=32
        )
    ref = fa.flash_attention(q, k, v, causal=causal, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_rejects_non_periodic_ring():
    from repro.core import _compat, errors, topology
    from repro.kernels.ring_attention import ops as ring_ops

    mesh = _compat.make_mesh((1,), ("ring",))
    cart = topology.CartComm(
        mesh, ("ring",), dims=(1,), periods=(False,), managed=False, tag="r0"
    )
    x = jnp.zeros((1, 8, 2, 4))
    with pytest.raises(errors.TopologyError):
        ring_ops.ring_attention(cart, x, x, x)


RING_PARITY = textwrap.dedent("""
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import _compat, topology
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.ring_attention import ops as ring_ops

    N = 8
    mesh = _compat.make_mesh((N,), ("ring",))
    cart = topology.CartComm(mesh, ("ring",), dims=(N,), periods=(True,),
                             managed=False, tag="ring-test")
    spec = P(None, "ring", None, None)

    def ring(q, k, v, *, causal, impl, global_len):
        def body(ql, kl, vl):
            return ring_ops.ring_attention(
                cart, ql, kl, vl, causal=causal, global_len=global_len,
                impl=impl, block_q=16, block_k=16)
        return _compat.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)

    def check(S, causal, impl, tol=5e-5):
        ks = jax.random.split(jax.random.PRNGKey(S), 3)
        q = jax.random.normal(ks[0], (2, S, 4, 16))
        k = jax.random.normal(ks[1], (2, S, 2, 16))
        v = jax.random.normal(ks[2], (2, S, 2, 16))
        pad = (-S) % N
        qp = jnp.pad(q, ((0,0),(0,pad),(0,0),(0,0)))
        kp = jnp.pad(k, ((0,0),(0,pad),(0,0),(0,0)))
        vp = jnp.pad(v, ((0,0),(0,pad),(0,0),(0,0)))
        with mesh:
            out = jax.jit(lambda a, b, c: ring(
                a, b, c, causal=causal, impl=impl, global_len=S))(qp, kp, vp)
        ref = fa.flash_attention(q, k, v, causal=causal, impl="ref")
        np.testing.assert_allclose(np.asarray(out)[:, :S], np.asarray(ref),
                                   atol=tol, rtol=tol)
        print("ok", S, causal, impl)

    for impl in ("ref", "pallas"):
        check(128, True, impl)        # even shards
        check(128, False, impl)
        check(101, True, impl)        # ragged tail: shard 6 partial, 7 empty
        check(101, False, impl)

    # gradient parity through the custom-VJP ring vs the dense reference
    S = 96
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, S, 2, 16))
    k = jax.random.normal(ks[1], (1, S, 2, 16))
    v = jax.random.normal(ks[2], (1, S, 2, 16))
    with mesh:
        g_ring = jax.jit(jax.grad(
            lambda a, b, c: ring(a, b, c, causal=True, impl="pallas",
                                 global_len=S).sum(), argnums=(0, 1, 2)
        ))(q, k, v)
    g_ref = jax.grad(
        lambda a, b, c: fa.flash_attention(a, b, c, causal=True,
                                           impl="ref").sum(), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)
    print("RING_PARITY_OK")
""")


SERVER_RING = textwrap.dedent("""
    import dataclasses
    import numpy as np
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.core._compat import make_mesh
    from repro.runtime.server import Request, Server, ServerConfig

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128,
                      vocab_size=256, dtype="float32")
    scfg = ServerConfig(max_batch=2, max_new_tokens=4)
    prompts = [np.arange(1, 33, dtype=np.int32), np.arange(5, 29, dtype=np.int32)]

    base = Server(cfg, ParallelConfig(), scfg, mesh)
    t0, _ = base.generate([Request(tokens=p.copy()) for p in prompts])
    ring = Server(cfg, dataclasses.replace(ParallelConfig(), ring_attention=True),
                  scfg, mesh)
    t1, _ = ring.generate([Request(tokens=p.copy()) for p in prompts])
    np.testing.assert_array_equal(t0, t1)
    print("SERVER_RING_OK")
""")


TRAINER_RING = textwrap.dedent("""
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.core._compat import make_mesh
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128,
                      vocab_size=256, dtype="float32")
    tcfg = TrainerConfig(steps=3, log_every=1, ring_attention=4)
    mesh = make_mesh((8,), ("data",))
    trainer = Trainer(cfg, ParallelConfig(), tcfg, mesh,
                      seq_len=96, global_batch=8)
    assert trainer.mesh.shape == {"data": 2, "model": 4}, trainer.mesh.shape
    assert trainer.pcfg.ring_attention
    result = trainer.run()
    assert result["final_step"] == 3
    losses = [m["loss"] for m in result["metrics"]]
    assert all(l == l and l < 100 for l in losses), losses
    print("TRAINER_RING_OK")
""")


def test_ring_parity_under_shard_map(subproc):
    assert "RING_PARITY_OK" in subproc(RING_PARITY, n=8)


def test_server_ring_prefill_matches_dense(subproc):
    assert "SERVER_RING_OK" in subproc(SERVER_RING, n=8, timeout=1200)


def test_trainer_ring_attention_mode(subproc):
    assert "TRAINER_RING_OK" in subproc(TRAINER_RING, n=8, timeout=1200)
