"""Elastic recovery cost: what one injected rank failure costs the job.

Two gated series (8 virtual devices, deterministic eviction schedule):

* ``elastic_recovery_steps`` — steps replayed per failure, i.e. the distance
  from the eviction back to the last *committed* manifest.  With
  ``checkpoint_every=2`` and the eviction one step past a save this is
  exactly 1 — any regression means the commit point or the restore-step
  bookkeeping drifted;
* ``elastic_rebuild_ratio`` — wall cost of the whole shrink path (revoke →
  ``Group.difference`` → fabric rebuild → restore → recompile) over a mean
  clean step.  Compile-dominated at smoke scale (the recompile IS most of
  it), so the gate gives it the same wide band as the other AOT-compile
  ratios.

    PYTHONPATH=src python -m benchmarks.elastic_bench [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "artifacts" / "bench"

CHILD = r"""
import json, statistics, tempfile, time
from repro.configs.base import ModelConfig, ParallelConfig
from repro.core import tool
from repro.core.communicator import Communicator
from repro.core.session import Session
from repro.runtime.faults import FaultInjector
from repro.runtime.trainer import Trainer, TrainerConfig

STEPS, EVICT_AT = 12, 7
cfg = ModelConfig(name="tiny", family="dense", num_layers=1, d_model=32,
                  num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                  vocab_size=64)
tcfg = TrainerConfig(steps=STEPS, lr=1e-3,
                     checkpoint_dir=tempfile.mkdtemp(prefix="elastic_bench_"),
                     checkpoint_every=2, log_every=1, seed=7)
world = Session.init().group("repro://world")
comm = Communicator.from_group(world, tag="repro://train", shape=(4, 2),
                               axis_names=("data", "model"))
inj = FaultInjector().evict_rank(EVICT_AT, 2)
t = Trainer(cfg, ParallelConfig(), tcfg, comm, seq_len=32, global_batch=12,
            injector=inj)

rebuild_wall = []
orig_shrink = t._shrink
def timed_shrink(evt):
    t0 = time.perf_counter()
    out = orig_shrink(evt)
    rebuild_wall.append(time.perf_counter() - t0)
    return out
t._shrink = timed_shrink

res = t.run()
assert res["final_step"] == STEPS and res["evictions"] == 1, res
recovery_steps = tool.pvar_read()["elastic:recovery_steps"]

# mean clean step: pre-eviction steady state (skip the warm-up step)
clean = [m["duration_s"] for m in res["metrics"] if 1 < m["step"] < EVICT_AT]
mean_clean = statistics.mean(clean)
print("RESULT " + json.dumps({
    "recovery_steps": recovery_steps,
    "rebuild_wall_s": rebuild_wall[0],
    "mean_clean_step_s": mean_clean,
    "rebuild_ratio": rebuild_wall[0] / max(mean_clean, 1e-9),
    "epoch": res["epoch"], "world_size": res["world_size"],
}))
"""


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="accepted for job-list symmetry")
    ap.parse_args(argv)

    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",  # virtual devices: a CPU measurement
        "PYTHONPATH": str(ROOT / "src"),
    }
    proc = subprocess.run(
        [sys.executable, "-c", CHILD],
        capture_output=True, text=True, env=env, timeout=1800, cwd=str(ROOT),
    )
    if proc.returncode != 0:
        print(f"elastic_bench FAILED\n{proc.stderr[-2000:]}")
        return 1
    row = None
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            row = json.loads(line[len("RESULT "):])
    if row is None:
        print("elastic_bench produced no RESULT line")
        return 1
    print(
        f"eviction cost: {row['recovery_steps']} step(s) replayed, "
        f"shrink-rebuild-restore {row['rebuild_wall_s']*1e3:.0f} ms "
        f"({row['rebuild_ratio']:.1f}x a clean {row['mean_clean_step_s']*1e3:.0f} ms step)"
    )
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / "elastic_bench.json").write_text(json.dumps(row, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
