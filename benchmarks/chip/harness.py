"""What every cell shares: the spec read from ``BENCHMARK.json`` and the
files it names (the model's family among them), the device check, the
compile cache, host spans, and the counters read around the window."""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: program counters that must not move inside a measured window
WINDOW_COUNTERS = (
    "trace:prefill_step", "trace:decode_step", "trace:insert_row",
    "trace:train_step", "engine:preempt",
)


def load_spec(workload: str, root: Path = ROOT) -> dict:
    """A cell and the files it names, found under ``root`` (a checkout);
    its configuration's family file is loaded, as ``spec["family"]``,
    before anything is built."""

    bench = json.loads((root / "BENCHMARK.json").read_text())
    here = root / HERE.relative_to(ROOT)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    return {
        "cell": cell,
        "config": config,
        "family": family(config, here),
        "traffic": json.loads((here / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads((here / "limits" / f"{workload}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


def family(config: dict, here: Path = HERE):
    """The family file that a configuration names, ``families/<family>.py``
    loaded by path; its interface is in ``families/dense_gqa.py``.  Refused
    where the configuration names no family or the file does not exist."""

    name = config.get("family")
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", name):
        raise ValueError(f"configuration {config.get('name')!r} names no family")
    path = here / "families" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"configuration {config.get('name')!r} names family "
                                f"{name!r}, and there is no {path}")
    spec = importlib.util.spec_from_file_location(f"bench_family_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def parallel_config(c: dict):
    from repro.configs import base

    return base.get_parallel(c["program"]["arch"])


def device_info(chips: int) -> dict:
    """The devices JAX found, refused unless they are accelerators with
    published peaks, as many as the cell asks for."""

    import jax

    from chip_peaks import peaks_for

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform == "cpu":
        raise RuntimeError("JAX found no accelerator")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks_for(d0.device_kind)
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def use_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache`` (a fixed path: it is part of
    the cache key).  Every program is cached, however quick to compile."""

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


_EVENTS = {"compiles": 0, "traces": 0, "cache_hits": 0}
_LISTENING = []


def _listen() -> None:
    """Count JAX's compile events, once per process (its listeners are
    process-wide and cannot be removed)."""

    if _LISTENING:
        return
    import jax

    def on_duration(name, _secs, **_kw):
        if name.endswith("backend_compile_duration"):
            _EVENTS["compiles"] += 1
        elif name.endswith("jaxpr_trace_duration"):
            _EVENTS["traces"] += 1

    def on_event(name, **_kw):
        if name.endswith("cache_hits"):
            _EVENTS["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _LISTENING.append(True)


class Counters:
    """Program counters and JAX compile events, read before and after the
    window: anything but zero there means a shape was compiled or a request
    preempted inside the measured time."""

    def __init__(self):
        _listen()

    def read(self) -> dict:
        from repro.core import tool

        pv = tool.pvar_read()
        return {**{k: int(pv.get(k, 0)) for k in WINDOW_COUNTERS}, **_EVENTS}

    @staticmethod
    def diff(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}


@contextlib.contextmanager
def span(name: str, on: bool):
    """A ``bench.*`` host span in the profiler's trace (a no-op untraced)."""

    if not on:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def quantile(values, q: float) -> float:
    """The ``q`` quantile by nearest rank (the smallest value with at least
    ``q`` of the sample at or below it); infinite values sort last."""

    import math

    v = sorted(values)
    if not v:
        return float("nan")
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def note(**fields) -> None:
    """A line of diagnostics on standard output, before the result."""

    print("bench: " + json.dumps(fields, default=float), flush=True)
