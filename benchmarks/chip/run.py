"""Run one cell of the benchmark once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and metrics are found by
name from ``BENCHMARK.json``.  Set-up (building, warming every shape the
window reaches, compiling) is timed as ``setup_s``; then the window runs for
``--seconds``.  With ``--trace 1`` the window is traced and the cell's
per-layer metrics are read from the trace; otherwise its end-to-end
metrics are reported.  Either way the outputs of the timed path are checked
against the plain reference once the window has closed.

The last line on standard output is the result, one JSON object; the
numbers compared, each beside its limit, are its last key and the last
lines on standard error.  Without an accelerator with published peaks, or
with fewer chips than the cell asks for, it prints no result and exits 3.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import harness  # noqa: E402


def reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check(spec: dict, res: dict, seed: int) -> dict:
    """The numbers the limits hold, from the reference once the window has
    closed and the program's state is freed."""

    c, mix, fam = spec["config"], spec["traffic"], spec["family"]
    if mix["driver"] == "serve":
        import serve_driver

        return serve_driver.reference_numbers(fam, c, mix, seed, res["picked"],
                                              res["last_step"])
    import output_check
    import train_driver

    ref = train_driver.reference(fam, c, mix, seed)
    return output_check.train_numbers(res["check"], ref)


def run_cell(spec: dict, seed: int, seconds: float, traced: bool, t0: float,
             device: dict, fault=None) -> dict:
    """One run of a cell; returns the result line.  ``fault`` plants a
    fault in the system under test (tests only).  Metrics are reported only
    for an accelerator."""

    import output_check
    from chip_peaks import PEAKS

    mix = spec["traffic"]
    if mix["driver"] == "serve":
        import serve_driver as driver
    else:
        import train_driver as driver
    res = driver.run(spec, seed, seconds, traced, t0, fault)
    e2e = driver.end_to_end(res, mix)
    harness.note(setup_s=res["setup_s"], in_window=res["in_window"],
                 counts=e2e["_counts"], warm_rows=res.get("warm_rows"),
                 window={k: v for k, v in e2e.items() if not k.startswith("_")})
    t = time.perf_counter()
    numbers = check(spec, res, seed)
    harness.note(numbers=numbers, check_s=time.perf_counter() - t)
    correct, checks = output_check.judge(numbers, spec["limits"])
    counts = e2e["_counts"]
    if mix["driver"] == "serve":
        attempted, failed = counts["due"], 0
    else:
        attempted = counts["steps"]
        failed = 0 if counts["losses_finite"] else counts["steps"]
        correct = correct and counts["losses_finite"]

    out_dev = {**device, "memory_peak_bytes": res["memory_peak_bytes"]}
    metrics, breakdown = {}, None
    on_chip = device["platform"] != "cpu"
    if traced:
        import trace_reduce

        paths = sorted(Path(res["trace_dir"]).rglob("*.xplane.pb"))
        tr = trace_reduce.reduce(*trace_reduce.load(str(paths[-1])))
        shutil.rmtree(res["trace_dir"], ignore_errors=True)
        out_dev.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        breakdown = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
        harness.note(programs=tr["programs"], spans=tr["spans"],
                     collective_s=tr["collective_s"])
        ctx = {"trace": tr, "window": res["window"], "config": spec["config"],
               "traffic": mix, "chips": spec["cell"]["chips"], "flops": spec["family"],
               "peaks": PEAKS.get(device["kind"])}
        for m in spec["per_layer"]:
            v = reader(m["name"])(ctx) if on_chip else None
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    elif on_chip:
        e2e["setup_s"] = res["setup_s"]
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": out_dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one cell of the chip benchmark.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_spec(args.workload)
    try:
        device = harness.device_info(spec["cell"]["chips"])
    except (RuntimeError, KeyError) as e:
        print(f"run: {e}; no result", file=sys.stderr)
        return 3
    harness.use_compile_cache()
    line = run_cell(spec, args.seed, args.seconds, bool(args.trace), T0, device)
    for name, ch in line["checks"].items():
        print(f"check {name}: {ch['value']} (limit {ch['limit']})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
