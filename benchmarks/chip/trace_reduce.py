"""From a profiler trace to the numbers the per-layer metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes.  Device planes are
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds the operations and
the ``XLA Modules`` line the compiled programs they belong to.  Host spans
are the benchmark's own ``bench.*`` annotations, on the same clock.

- busy: the union of operation intervals inside the ``bench.window`` span,
  averaged over the chips; idle is the window less busy;
- device ops: seconds per ``program:operation``, summed over the window;
- idle gaps: the longest stretches with no operation on chip 0, each named
  by the innermost ``bench.*`` span around its midpoint.  The device clock
  runs some milliseconds apart from the host's in these traces, so the
  offset is estimated first and every device time moved by it: the host
  sees a ``bench.step`` end just after the device finishes that step's
  work, so the offset is the middle of those that put the most device work
  endings within 1 ms before a step's end;
- programs: executions and seconds per compiled program;
- spans: per ``bench.*`` name, count and seconds (host clock);
- collectives: seconds of collective operations, and the part of them with
  no other operation running on that chip.
"""

from __future__ import annotations

import re

COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def program_name(module: str) -> str:
    name = re.sub(r"\(.*\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(merged, s, e) -> int:
    """Length of [s, e) covered by the merged, sorted intervals."""

    tot = 0
    for a, b in merged:
        if b <= s:
            continue
        if a >= e:
            break
        tot += min(b, e) - max(a, s)
    return tot


def subtract(merged, others):
    """Length of ``merged`` not covered by ``others`` (both merged)."""

    return sum((b - a) - overlap(others, a, b) for a, b in merged)


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""

    return text.split(" = ", 1)[0].lstrip("%")


def clock_offset(merged, step_ends, lo=-10e6, hi=10e6, grid=10e3, tail=1e6) -> float:
    """ns to add to device times to put them on the host clock: of the
    offsets that put the most device busy-interval ends within ``tail``
    before a host ``bench.step`` end, the middle one."""

    import bisect

    ends = sorted(b for _, b in merged)
    if not ends or not step_ends:
        return 0.0
    scores = []
    for k in range(int((hi - lo) / grid) + 1):
        d = lo + k * grid
        n = 0
        for b in step_ends:
            i = bisect.bisect_right(ends, b - d)
            if i and ends[i - 1] >= b - d - tail:
                n += 1
        scores.append((n, d))
    top = max(n for n, _ in scores)
    if top == 0:
        return 0.0
    best = [d for n, d in scores if n == top]
    return best[len(best) // 2]


def load(path: str):
    """(host spans, device planes) as plain tuples: spans are (name, start,
    end); a device plane is (ops, modules), each a list of (name, start,
    end).  Times in ns."""

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        elif DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": mods}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append((op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns))
            devices.append((ops, mods))
    return spans, devices


def reduce(spans, devices, top: int = 10, offset: float | None = None) -> dict:
    """``offset`` (ns, device to host clock) is estimated when not given."""

    win = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not win or not devices:
        raise ValueError("the trace has no bench.window span or no device plane")
    w0, w1 = win[0]
    inner = sorted(((n, s, e) for n, s, e in spans if n != "bench.window"),
                   key=lambda x: x[1])
    if offset is None:
        offset = clock_offset(union([(s, e) for _, s, e in devices[0][0] if e > s]),
                              [e for n, _, e in inner if n == "bench.step"])
    devices = [([(n, s + offset, e + offset) for n, s, e in ops],
                [(n, s + offset, e + offset) for n, s, e in mods]) for ops, mods in devices]
    n_dev = len(devices)
    busy_total, op_secs, prog, coll_s, exposed_s = 0, {}, {}, 0, 0
    busy0 = None
    for d, (ops, mods) in enumerate(devices):
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
        mods = sorted((n, s, e) for n, s, e in mods if e > w0 and s < w1)
        mods = sorted(mods, key=lambda m: m[1])
        merged = union([(s, e) for _, s, e in ops if e > s])
        busy_total += sum(b - a for a, b in merged)
        if d == 0:
            busy0 = merged
        for n, s, e in mods:
            p = prog.setdefault(program_name(n), {"count": 0, "seconds": 0.0})
            p["count"] += 1 / n_dev
            p["seconds"] += (min(e, w1) - max(s, w0)) * 1e-9 / n_dev
        starts = [m[1] for m in mods]
        j = 0
        for n, s, e in sorted(ops, key=lambda o: o[1]):
            while j + 1 < len(starts) and starts[j + 1] <= s:
                j += 1
            owner = program_name(mods[j][0]) if mods and mods[j][1] <= s < mods[j][2] else "?"
            key = f"{owner}:{n}"
            op_secs[key] = op_secs.get(key, 0.0) + (e - s) * 1e-9 / n_dev
        coll = union([(s, e) for n, s, e in ops if COLLECTIVE.search(n) and e > s])
        rest = union([(s, e) for n, s, e in ops if not COLLECTIVE.search(n) and e > s])
        coll_s += sum(b - a for a, b in coll) / n_dev
        exposed_s += subtract(coll, rest) / n_dev

    gaps, prev = [], w0
    for a, b in busy0 + [[w1, w1]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    named = []
    for s, e in gaps:
        mid, name = (s + e) / 2, "bench.window"
        for n, a, b in inner:
            if a <= mid < b:
                name = n       # later starts are nested deeper
        named.append([name, (e - s) * 1e-9])
    named.sort(key=lambda g: -g[1])

    span_stats = {}
    for n, s, e in inner:
        st = span_stats.setdefault(n, {"count": 0, "seconds": 0.0})
        st["count"] += 1
        st["seconds"] += (e - s) * 1e-9
    ranked = sorted(op_secs.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total * 1e-9 / n_dev,
        "chips": n_dev,
        "device_ops": [[k, v] for k, v in ranked[:top]],
        "idle_gaps": named[:top],
        "programs": prog,
        "spans": span_stats,
        "clock_offset_s": offset * 1e-9,
        "collective_s": coll_s * 1e-9,
        "exposed_collective_s": exposed_s * 1e-9,
    }
