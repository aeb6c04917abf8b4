"""The program's own spans in a profiler trace, beside the device's work.

    python3 benchmarks/chip/program_spans.py --xplane <trace.xplane.pb>
    python3 benchmarks/chip/program_spans.py --workload <cell> --seed <n> \
        --seconds <s> [--out <report.json>]

The first reduces a trace that is there; the second runs one traced window
of the cell (as ``run.py --trace 1`` does, without the check against the
reference; on an accelerator only) and reduces its trace.  Either prints
the report, one JSON object, less its span list; ``--out`` writes it whole.

The spans are ``repro.*`` host events (``repro.core.tool.span``) with their
stats.  The report holds, over the ``bench.window`` span:

- spans: per name the count, seconds and self-seconds (less the spans
  nested in it on its thread); list: each span with its stats and parent;
- idle_share: per name, the share of the window in which the device is idle
  while the host is inside such a span;
- idle_gaps, idle_by_span: the longest stretches with no operation on chip
  0, and the idle seconds per span, each gap named by the innermost span,
  ``bench.*`` or ``repro.*``, around its midpoint;
- metrics: the per-layer numbers the spans give (``dispatch_us``,
  ``sched_ms_per_step``, ``step_idle``, ``prefill_useful``,
  ``trainer_host_ms_per_step``), those the trace holds;
- clock: the device-to-host offset fitted on the ends of the host's waits
  for the device (``repro.engine.wait``, ``repro.trainer.wait``), the
  ``bench.step`` fit of ``trace_reduce``, and the offsets causality allows.

Device times are put on the host's clock by the wait fit where the trace
has waits, else by ``trace_reduce``'s.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import trace_reduce  # noqa: E402
from trace_reduce import overlap, program_name, union  # noqa: E402

WAITS = ("repro.engine.wait", "repro.trainer.wait")
#: the program each wait waits for, dispatched by ``repro.request.start``
WAITED = {"repro.engine.wait": "decode_step", "repro.trainer.wait": "step_fn"}


def load(path: str) -> list:
    """The ``repro.*`` host events: (name, start, end, stats, thread), ns."""

    from jax.profiler import ProfileData

    out = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        if not plane.name.startswith("/host:"):
            continue
        for t, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                dict(ev.stats), f"{p}.{t}"))
    return out


def nest(program) -> list:
    """The index of each span's parent (the innermost span of its thread
    that holds it), or None."""

    parent = [None] * len(program)
    stack = {}
    for i in sorted(range(len(program)), key=lambda i: (program[i][1], -program[i][2])):
        _, s, e, _, th = program[i]
        st = stack.setdefault(th, [])
        while st and program[st[-1]][2] < e:
            st.pop()
        parent[i] = st[-1] if st else None
        st.append(i)
    return parent


def less_nested(spans: list, outer: str, inner: tuple) -> list:
    """Seconds of each ``outer`` span of a report's ``list``, less those of
    the spans named in ``inner`` nested anywhere inside it."""

    left = {i: sp["seconds"] for i, sp in enumerate(spans) if sp["name"] == outer}
    for sp in spans:
        if sp["name"] in inner:
            i = sp["parent"]
            while i is not None and i not in left:
                i = spans[i]["parent"]
            if i is not None:
                left[i] -= sp["seconds"]
    return list(left.values())


def clock_bracket(program, mods, offset: float):
    """The offsets (ns, device to host) that causality allows, as
    (lowest, highest): the k-th dispatch of a waited-for program (a
    ``repro.request.start`` span with its ``name``) starts before the k-th
    execution of that program on the chip starts, and the first wait that
    starts after that dispatch ends after that execution ends.  None where
    the trace holds no such pair, or the counts of dispatches and
    executions differ."""

    lo, hi, pairs = None, None, 0
    for wait, prog in WAITED.items():
        disp = sorted(s for n, s, _, st, _ in program
                      if n == "repro.request.start" and st.get("name") == prog)
        runs = sorted((s, e) for n, s, e in mods if program_name(n) == prog)
        waits = sorted((s, e) for n, s, e, _, _ in program if n == wait)
        if not disp or len(disp) != len(runs):
            continue
        for d, (s, e) in zip(disp, runs):
            lo = d - s if lo is None else max(lo, d - s)
            after = [we for ws, we in waits if ws >= d]
            if after:
                hi = after[0] - e if hi is None else min(hi, after[0] - e)
                pairs += 1
    if lo is None or hi is None:
        return None
    return {"low_s": lo * 1e-9, "high_s": hi * 1e-9, "pairs": pairs,
            "inside": lo <= offset <= hi}


def reduce(spans, devices, program, top: int = 10, offset: float | None = None) -> dict:
    """The report, from ``trace_reduce.load``'s spans and devices and
    :func:`load`'s program spans; ``offset`` (ns, device to host clock) is
    fitted when not given."""

    w0, w1 = next((s, e) for n, s, e in spans if n == "bench.window")
    base = trace_reduce.reduce(spans, devices, offset=offset)
    ops0 = union([(s, e) for _, s, e in devices[0][0] if e > s])
    wait_ends = [e for n, _, e, _, _ in program if n in WAITS]
    if offset is None:
        offset = (trace_reduce.clock_offset(ops0, wait_ends) if wait_ends
                  else base["clock_offset_s"] * 1e9)
    busy = [union([(max(s + offset, w0), min(e + offset, w1)) for _, s, e in ops
                   if e > s and e + offset > w0 and s + offset < w1])
            for ops, _ in devices]

    parent = nest(program)
    keep = [i for i, (_, s, e, _, _) in enumerate(program) if e > w0 and s < w1]
    index = {i: k for k, i in enumerate(keep)}
    listed = []
    for i in keep:
        n, s, e, st, _ = program[i]
        s, e = max(s, w0), min(e, w1)
        listed.append({"name": n, "start_s": (s - w0) * 1e-9, "seconds": (e - s) * 1e-9,
                       "stats": st, "parent": index.get(parent[i])})
    per = {}
    for sp in listed:
        p = per.setdefault(sp["name"], {"count": 0, "seconds": 0.0, "self_seconds": 0.0})
        p["count"] += 1
        p["seconds"] += sp["seconds"]
        p["self_seconds"] += sp["seconds"]
    for sp in listed:
        if sp["parent"] is not None:
            per[listed[sp["parent"]]["name"]]["self_seconds"] -= sp["seconds"]
    idle_share = {}
    for n in per:
        inside = union([(max(s, w0), min(e, w1)) for m, s, e, _, _ in program
                        if m == n and e > w0 and s < w1])
        held = sum(b - a for a, b in inside)
        idle_share[n] = sum(held - sum(overlap(b, a, c) for a, c in inside)
                            for b in busy) / len(busy) / (w1 - w0)

    around = sorted([(n, s, e) for n, s, e in spans if n != "bench.window"]
                    + [(n, s, e) for n, s, e, _, _ in program], key=lambda x: x[1])
    gaps, prev = [], w0
    for a, b in busy[0] + [[w1, w1]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    named, by_span = [], {}
    for s, e in gaps:
        mid, name = (s + e) / 2, "bench.window"
        for n, a, b in around:
            if a <= mid < b:
                name = n       # later starts are nested deeper
        named.append([name, (e - s) * 1e-9])
        by_span[name] = by_span.get(name, 0.0) + (e - s) * 1e-9
    named.sort(key=lambda g: -g[1])

    report = {"window_s": (w1 - w0) * 1e-9,
              "busy_s": sum(b - a for m in busy for a, b in m) * 1e-9 / len(busy),
              "spans": per, "list": listed, "idle_share": idle_share,
              "idle_gaps": named[:top],
              "idle_by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
              "clock": {"offset_s": offset * 1e-9,
                        "bench_step_offset_s": base["clock_offset_s"],
                        "causal": clock_bracket(program, devices[0][1], offset)}}
    report["metrics"] = metrics(report)
    return report


def metrics(report: dict) -> dict:
    """The per-layer numbers a report gives, those its spans hold."""

    per, listed, out = report["spans"], report["list"], {}
    p = per.get("repro.request.start")
    if p and p["count"]:
        # host time of one persistent dispatch (PersistentRequest.__call__)
        out["dispatch_us"] = 1e6 * p["seconds"] / p["count"]
    left = less_nested(listed, "repro.engine.step",
                       ("repro.engine.first_token", "repro.engine.wait"))
    if left:
        # the engine's own host time a step, less its waits for the device
        out["sched_ms_per_step"] = 1e3 * sum(left) / len(left)
    if "repro.engine.step" in report["idle_share"]:
        out["step_idle"] = 100.0 * report["idle_share"]["repro.engine.step"]
    adm = [sp["stats"] for sp in listed if sp["name"] == "repro.engine.admit"]
    slots = sum(st["padded_rows"] * st["length"] for st in adm)
    if slots:
        # real prompt tokens over the prefill's slots (rows x bucket)
        out["prefill_useful"] = 100.0 * sum(st["real_tokens"] for st in adm) / slots
    left = less_nested(listed, "repro.trainer.step", ("repro.trainer.wait",))
    if left:
        out["trainer_host_ms_per_step"] = 1e3 * sum(left) / len(left)
    return out


def read(path: str) -> dict:
    return reduce(*trace_reduce.load(path), load(path))


def traced_window(workload: str, seed: int, seconds: float) -> dict:
    """One traced window of the cell on this machine's chips, reduced."""

    import shutil

    import harness

    spec = harness.load_spec(workload)
    harness.device_info(spec["cell"]["chips"])
    harness.use_compile_cache()
    if spec["traffic"]["driver"] == "serve":
        import serve_driver as driver
    else:
        import train_driver as driver
    res = driver.run(spec, seed, seconds, True, T0)
    try:
        report = read(str(sorted(Path(res["trace_dir"]).rglob("*.xplane.pb"))[-1]))
    finally:
        shutil.rmtree(res["trace_dir"], ignore_errors=True)
    report["setup_s"] = res["setup_s"]
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Reduce the program's spans in a trace.")
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--xplane")
    src.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    if args.xplane:
        report = read(args.xplane)
    else:
        try:
            report = traced_window(args.workload, args.seed, args.seconds)
        except (RuntimeError, KeyError) as e:
            print(f"program_spans: {e}; no report", file=sys.stderr)
            return 3
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(report))
    print(json.dumps({k: v for k, v in report.items() if k != "list"}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
