"""Smoke run of the serve and train paths on a TPU, at phi4-mini widths.

    python chip_smoke.py             # one chip: serve, then train
    python chip_smoke.py --chips 4   # the trainer's default 4-chip layout
                                     # against the same first step on one chip

One process, no children; it refuses to run anywhere but on a TPU.

* **serve** — the whole ``phi4_mini_3_8b`` (32 layers, published widths,
  random weights from ``--seed``) behind ``Server`` + ``Engine``, built as
  ``launch/serve.py --continuous-batching`` builds them: 8 requests of 512
  tokens, 32 new tokens each, 4 slots, greedy.  Every decode step's logits
  must be finite, and the last step's logits, read through the KV cache,
  must agree with a full forward pass over prompt + generated tokens.
* **train** — ``Trainer.run()`` as ``launch/train.py`` builds it (no
  checkpoint dir, the launcher default) on ``phi4_mini_3_8b`` at published
  widths and the whole vocabulary, depth cut to ``TRAIN_LAYERS``, for 5
  steps: every loss finite, no restart, eviction or checkpoint failure.
* **--chips 4** — ``--mesh auto`` over four chips (every device on the FSDP
  data axis) beside the same first step on one chip, same seed and batch:
  the losses and gradient norms agree, and every parameter sits on all
  four devices.

The earlier lines are a smoke observation, not a benchmark: times include
a cold or warm compile cache as it happens to be.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
a failed check raises, and the run exits nonzero without it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "phi4_mini_3_8b"

# serve traffic: what launch/serve.py --continuous-batching runs by default
# in slots (max_batch 4), at a chat-sized prompt
REQUESTS, PROMPT_LEN, NEW_TOKENS, SLOTS = 8, 512, 32, 4

# The decode path reads K/V from a bf16 cache and attends one token at a
# time; the full forward attends over the whole sequence at once.  Both run
# bf16 matmuls with fp32 accumulation, so they differ by bf16 rounding: a
# unit roundoff of 2^-8 per layer, growing as sqrt(32) ~ 5.7 units over 32
# independent layers.  The bound, 8 units, is on the largest logit
# difference relative to the largest logit; a cache read at a wrong
# position or from a wrong row differs by order 1.
SERVE_REL_TOL = 2.0**-5

# Train: depth cut so that params (bf16), AdamW moments (fp32), gradients
# and the 200,064-way logits of a 4 x 512 batch fit one 16 GiB v5e.  The
# compiled step at 4 layers needs 12.4 GiB (memory_analysis() of the step
# compiled for a described v5e); 32 layers would need ~40 GiB.
TRAIN_LAYERS, TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 4, 5, 4, 512

# One chip against four on the same first step: the weights are gathered
# whole either way, but the four-chip step splits the batch and sums bf16
# gradient shards across chips in another order.
FIRST_STEP_REL_TOL = 2e-2


class SmokeFailure(RuntimeError):
    pass


def require(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def observe(**fields) -> None:
    print(json.dumps({"smoke_observation": True, **fields}), flush=True)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def serve_phase(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import base
    from repro.launch.mesh import make_host_communicator
    from repro.runtime.engine import Engine, EngineConfig
    from repro.runtime.server import Request, Server, ServerConfig

    pcfg = base.get_parallel(ARCH)
    t0 = time.perf_counter()
    server = Server(
        cfg, pcfg,
        ServerConfig(max_batch=SLOTS, max_new_tokens=NEW_TOKENS, temperature=0.0,
                     seed=seed),
        make_host_communicator(1, 1),
    )
    jax.block_until_ready(server.params)
    t_init = time.perf_counter() - t0
    eng = Engine(server, EngineConfig(prompt_bucket=PROMPT_LEN))
    t_engine = time.perf_counter() - t0 - t_init

    rng = np.random.default_rng(seed)
    handles = [
        eng.submit(Request(tokens=rng.integers(
            1, cfg.vocab_size, size=(PROMPT_LEN,), dtype=np.int32)))
        for _ in range(REQUESTS)
    ]
    finite = jnp.bool_(True)
    step_s, last_done = [], []
    while eng.waiting or any(r is not None for r in eng.active):
        ts = time.perf_counter()
        done = eng.step()       # returns after the sampled tokens reach the host
        step_s.append(time.perf_counter() - ts)
        finite = finite & jnp.all(jnp.isfinite(eng.logits))
        if done:
            last_done = done
    require(bool(finite), "a decode step produced non-finite logits")
    require(
        len(eng.finished) == REQUESTS
        and all(len(h.generated) == NEW_TOKENS for h in handles),
        f"not every request was answered in full: "
        f"{[len(h.generated) for h in handles]}",
    )

    # the final step retired one request per slot, in slot order, so row i
    # of the engine's last logits belongs to last_done[i]
    require(len(last_done) == SLOTS, f"last step retired {len(last_done)} rows")
    seqs = np.stack([
        np.concatenate([h.tokens, np.asarray(h.generated[:-1], np.int32)])
        for h in last_done
    ])
    full = jax.jit(lambda p, b: server.bundle.prefill(p, b, pcfg, None)[0])
    with server.mesh:
        ref = full(server.params, {"tokens": jnp.asarray(seqs)})
    ref = np.asarray(ref[:, -1, : cfg.vocab_size], np.float32)
    got = np.asarray(eng.logits[:, -1, : cfg.vocab_size], np.float32)
    require(np.isfinite(ref).all(), "full-forward logits are not finite")
    rel = float(np.abs(got - ref).max() / np.abs(ref).max())
    require(rel <= SERVE_REL_TOL,
            f"cached decode logits differ from the full forward by {rel:.3g} "
            f"of the largest logit (bound {SERVE_REL_TOL})")

    steady = sorted(step_s[1:])
    observe(
        phase="serve", arch=cfg.name, layers=cfg.num_layers, cut="none",
        requests=REQUESTS, prompt_len=PROMPT_LEN, new_tokens=NEW_TOKENS,
        slots=SLOTS, engine=eng.stats(),
        init_s=t_init, engine_init_s=t_engine, first_step_s=step_s[0],
        median_step_s=steady[len(steady) // 2],
        cache_vs_full_rel_err=rel, rel_tol=SERVE_REL_TOL,
        argmax_agrees=int((ref.argmax(-1) == [h.generated[-1] for h in last_done]).sum()),
        peak_bytes_in_use=peak_bytes(jax.devices()[0]),
    )


def trainer_for(cfg, comm, steps: int, seed: int):
    """The trainer ``launch/train.py`` builds: no checkpoint dir, no plan,
    no fault injection."""

    from repro.configs import base
    from repro.runtime.trainer import Trainer, TrainerConfig

    tcfg = TrainerConfig(
        steps=steps, checkpoint_dir=None, checkpoint_every=max(1, steps // 2),
        log_every=1, seed=seed,
    )
    return Trainer(cfg, base.get_parallel(ARCH), tcfg, comm,
                   seq_len=TRAIN_SEQ, global_batch=TRAIN_BATCH)


def train_phase(cfg, seed: int) -> None:
    import math

    import jax

    from repro.launch.mesh import make_host_communicator

    t0 = time.perf_counter()
    res = trainer_for(cfg, make_host_communicator(1, 1), TRAIN_STEPS, seed).run()
    wall = time.perf_counter() - t0
    losses = [m["loss"] for m in res["metrics"]]
    require(res["final_step"] == TRAIN_STEPS and len(losses) == TRAIN_STEPS,
            f"trainer stopped at step {res['final_step']}")
    require(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    for k in ("restarts", "evictions", "ckpt_failures"):
        require(res[k] == 0, f"{k} = {res[k]}")
    observe(
        phase="train", arch=cfg.name, layers=cfg.num_layers,
        cut=f"depth {cfg.num_layers} of 32", batch=TRAIN_BATCH, seq=TRAIN_SEQ,
        losses=losses, step_s=[m["duration_s"] for m in res["metrics"]],
        run_s=wall, restarts=res["restarts"], evictions=res["evictions"],
        ckpt_failures=res["ckpt_failures"],
        peak_bytes_in_use=peak_bytes(jax.devices()[0]),
    )


def four_chip_phase(cfg, seed: int) -> None:
    import jax

    from repro.launch.mesh import make_host_communicator

    one = trainer_for(cfg, make_host_communicator(1, 1), 1, seed).run()
    first = one["metrics"][0]

    four = trainer_for(cfg, make_host_communicator(), 1, seed)   # --mesh auto
    devices = set(jax.devices())
    require(four.comm.size() == 4 and len(devices) == 4,
            f"--chips 4 needs four devices, the mesh holds {four.comm.size()}")
    params = four.init_state()[0]
    leaves = jax.tree_util.tree_leaves_with_path(params)
    off = [jax.tree_util.keystr(p) for p, x in leaves if x.sharding.device_set != devices]
    require(not off, f"parameters not on all four devices: {off}")
    split = sum(1 for _, x in leaves if not x.sharding.is_fully_replicated)
    embed = params["embed"]
    require(embed.addressable_shards[0].data.size * 4 == embed.size,
            "the embedding is not split four ways")
    del params
    res = four.run()
    got = res["metrics"][0]
    for k in ("loss", "grad_norm"):
        rel = abs(got[k] - first[k]) / abs(first[k])
        require(rel <= FIRST_STEP_REL_TOL,
                f"first-step {k}: 4 chips {got[k]} vs 1 chip {first[k]} "
                f"(rel {rel:.3g} > {FIRST_STEP_REL_TOL})")
    require(res["restarts"] == 0 and res["ckpt_failures"] == 0, "four-chip run faulted")
    observe(
        phase="train_4chip_vs_1chip", arch=cfg.name, layers=cfg.num_layers,
        cut=f"depth {cfg.num_layers} of 32", batch=TRAIN_BATCH, seq=TRAIN_SEQ,
        mesh=dict(four.comm.mesh.shape), params_split=split,
        params_total=len(leaves),
        one_chip={k: first[k] for k in ("loss", "grad_norm", "duration_s")},
        four_chips={k: got[k] for k in ("loss", "grad_norm", "duration_s")},
        rel_tol=FIRST_STEP_REL_TOL,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); nothing run",
              file=sys.stderr)
        return 2

    from repro.configs import base
    from repro.launch import use_compile_cache

    use_compile_cache()
    count = len(jax.devices())
    observe(device_kind=dev.device_kind, device_count=count, chips=args.chips)
    cfg = base.get_config(ARCH)
    cut = dataclasses.replace(cfg, num_layers=TRAIN_LAYERS)
    if args.chips == 4:
        four_chip_phase(cut, args.seed)
    else:
        serve_phase(cfg, args.seed)
        train_phase(cut, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
