"""End-to-end train/serve throughput on the host devices (smoke-scale
models; the production numbers are the §Roofline projections).

    PYTHONPATH=src python -m benchmarks.train_throughput [--arch gemma2_9b]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "artifacts" / "bench"

CHILD = r"""
import json, sys, time
import jax
from repro.configs import base
from repro.launch.mesh import make_host_mesh
from repro.runtime.trainer import Trainer, TrainerConfig

arch, steps, batch, seq = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
persistent = sys.argv[5] == "persistent"
ring = int(sys.argv[6]) if len(sys.argv) > 6 else 0
cfg = base.get_smoke_config(arch)
pcfg = base.get_parallel(arch)
mesh = make_host_mesh()
t = Trainer(cfg, pcfg,
            TrainerConfig(steps=steps, log_every=steps, persistent=persistent,
                          ring_attention=ring),
            mesh, seq_len=seq, global_batch=batch)
mesh = t.mesh    # ring/pipeline modes re-form the communicator (and mesh)
pcfg = t.pcfg
params, opt_state = t.init_state()
step_fn = t.compile(params, opt_state)
b = t.pipeline.device_batch(0, mesh, pcfg)
params, opt_state, m = step_fn(params, opt_state, b)   # warm
jax.block_until_ready(m["loss"])
t0 = time.perf_counter()
for i in range(steps):
    b = t.pipeline.device_batch(i, mesh, pcfg)
    params, opt_state, m = step_fn(params, opt_state, b)
jax.block_until_ready(m["loss"])
dt = time.perf_counter() - t0
print("RESULT " + json.dumps({
    "arch": arch, "steps": steps, "s_per_step": dt / steps,
    "tokens_per_s": batch * seq * steps / dt,
    "steps_per_s": steps / dt,
    "final_loss": float(m["loss"]),
    "seq": seq, "ring": ring,
    "mode": "ring" if ring > 1 else ("persistent" if persistent else "per-call"),
}))
"""


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="*", default=["gemma2_9b", "mamba2_2_7b", "grok_1_314b"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--per-call", dest="per_call", action="store_true",
                    help="plain-jit step instead of the persistent engine")
    ap.add_argument("--ring", type=int, default=0,
                    help="ring-attention mode: fold the devices onto a "
                    "(data, ring) cart of this ring size and shard the "
                    "sequence — run at --seq lengths one device's KV budget "
                    "cannot hold (reports ring_steps_per_s)")
    args = ap.parse_args(argv)
    if args.ring > 1:
        # the long-context configuration: sequence sharded over the ring,
        # dense family only (the ring path lives in the attention layers)
        args.archs = [a for a in args.archs if a == "gemma2_9b"] or ["gemma2_9b"]

    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",  # virtual devices: a CPU measurement
        "PYTHONPATH": str(ROOT / "src"),
    }
    rows = []
    failed = []
    for arch in args.archs:
        proc = subprocess.run(
            [sys.executable, "-c", CHILD, arch, str(args.steps), str(args.batch),
             str(args.seq), "per-call" if args.per_call else "persistent",
             str(args.ring)],
            capture_output=True, text=True, env=env, timeout=1800, cwd=str(ROOT),
        )
        if proc.returncode != 0:
            print(f"{arch}: FAILED\n{proc.stderr[-1500:]}")
            failed.append(arch)
            continue
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT "):
                r = json.loads(line[len("RESULT "):])
                rows.append(r)
                print(f"{arch}: {r['s_per_step']*1e3:.1f} ms/step, "
                      f"{r['tokens_per_s']:.0f} tok/s (smoke scale, 8 virtual devs)")
    OUT.mkdir(parents=True, exist_ok=True)
    name = "train_throughput_ring.json" if args.ring > 1 else "train_throughput.json"
    (OUT / name).write_text(json.dumps(rows, indent=1))
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
