"""Readings that set a cell's limits, on the chip: the program's numbers
over many seeds (the lower reading), and the control's and each planted
fault's over the first few (the upper reading).

    python3 benchmarks/chip/control.py --workload phi4_serve_chat \
        --seeds 11,12,...,22 --control-seeds 3 --seconds 30

- serving: each seed runs the cell at its own load for ``--seconds`` and
  compares the sampled requests as a run does; for the control seeds the
  reference is also run in float8 over the same prompts and served tokens,
  and the gap of the token it ranks first is read, and the gap of each
  served token altered to the next id (the fault of a token altered where
  it is produced, planted in the served tokens);
- training: each seed runs set-up and the first steps (no window); for the
  control seeds the reference in float8, and the reference with half of
  each batch left out (the mean over the rest), are compared with the
  float32 reference as if they were the program.  A state left unchanged
  reads 1 on ``change_gap`` by construction and is not run.

One JSON line per seed.  The benchmark's own runs never run this."""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import harness  # noqa: E402


def serve_seed(spec, seed, seconds, control):
    import serve_driver

    res = serve_driver.run(spec, seed, seconds, False, time.perf_counter())
    out = serve_driver.reference_numbers(spec["family"], spec["config"], spec["traffic"],
                                         seed, res["picked"], res["last_step"], control)
    return {**out, "in_window": res["in_window"]}


def train_seed(spec, seed, control):
    import output_check
    import train_driver

    c, job, fam = spec["config"], spec["traffic"], spec["family"]
    res = train_driver.run(spec, seed, 0.0, False, time.perf_counter())
    ref = train_driver.reference(fam, c, job, seed)
    out = {k: v for k, v in output_check.train_numbers(res["check"], ref).items()}
    out["losses"] = res["check"]["losses"]
    out["ref_losses"] = ref["losses"]
    if control:
        for name, kw in (("control", {"fp8": True}), ("half_batch", {"rows_used": 0.5})):
            low = train_driver.reference(fam, c, job, seed, **kw)
            out[name] = {k: v for k, v in output_check.train_numbers(low, ref).items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    spec = harness.load_spec(args.workload)
    harness.device_info(spec["cell"]["chips"])
    harness.use_compile_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        control = i < args.control_seeds
        if spec["traffic"]["driver"] == "serve":
            out = serve_seed(spec, seed, args.seconds, control)
        else:
            out = train_seed(spec, seed, control)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "wall_s": time.perf_counter() - t, **out}, default=float), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
