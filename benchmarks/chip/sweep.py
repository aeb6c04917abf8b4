"""Sweep a serving cell's open-loop rate on the chip, to find the knee: the
highest rate the engine sustains without a growing queue.

    python3 benchmarks/chip/sweep.py --workload phi4_serve_chat \
        --rates 2,3,4,5,6 --seconds 30 --seed 1

One process builds and warms the cell once; each rate gets a fresh engine
over the same server and its own window.  Prints one JSON line per rate:
TTFT p50/p90, the queue left at the close, and requests finished per
second.  Used once, when a cell's rate is chosen; the benchmark's own runs
never sweep."""

from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import harness  # noqa: E402
import serve_driver  # noqa: E402
import traffic_gen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec = harness.load_spec(args.workload)
    harness.device_info(spec["cell"]["chips"])
    harness.use_compile_cache()
    from repro.runtime.engine import Engine

    c, mix = spec["config"], spec["traffic"]
    server, engine = serve_driver.build(spec["family"], c, mix, args.seed)
    serve_driver.warm(engine, c["vocab_size"])
    ecfg = engine.ecfg
    for rate in [float(r) for r in args.rates.split(",")]:
        del engine
        gc.collect()
        engine = Engine(server, ecfg)
        m = copy.deepcopy(mix)
        m["rate_per_s"] = rate
        n = int(rate * args.seconds * 1.3) + m["block"]
        reqs = traffic_gen.serve_requests(m, args.seed, n, c["vocab_size"])
        t = time.perf_counter()
        win = serve_driver.window(engine, m, reqs, args.seconds, False)
        e2e = serve_driver.end_to_end({"window": win}, m)
        ttft = sorted((r["times"][0] - r["due"]) if r["times"] else float("inf")
                      for r in win["requests"])
        done = sum(1 for r in win["requests"] if "finished" in r)
        print(json.dumps({
            "rate_per_s": rate, "ttft_p50_s": harness.quantile(ttft, 0.5),
            "ttft_p90_s": e2e["ttft_p90_s"], "itl_p99_s": e2e["itl_p99_s"],
            "waiting_at_close": len(engine.waiting),
            "finished_per_s": done / win["seconds"], "due": win["due"],
            "wall_s": time.perf_counter() - t,
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
