"""What every cell shares: the spec read from ``BENCHMARK.json`` and the
files it names, the program's model configuration, the device check, the
compile cache, host spans, and the counters read around the window."""

from __future__ import annotations

import contextlib
import json
import os
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: program counters that must not move inside a measured window
WINDOW_COUNTERS = (
    "trace:prefill_step", "trace:decode_step", "trace:insert_row",
    "trace:train_step", "engine:preempt",
)


def load_spec(workload: str, root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "cell": cell,
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads((HERE / "limits" / f"{workload}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


def program_config(c: dict):
    """The program's ModelConfig for a configuration file.  Refuses what
    the program cannot run as the file states it."""

    from repro.configs.base import ModelConfig

    import math

    if c.get("partial_rotary_factor", 1.0) != 1.0 or c.get("rope_scaling"):
        raise ValueError("the program rotates whole heads with plain RoPE")
    plain = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0, "logits_scaling": 1.0,
             "attention_multiplier": 1.0 / math.sqrt(c["head_dim"])}
    for k, v in plain.items():
        if not math.isclose(c.get(k, v), v, rel_tol=1e-12):
            raise ValueError(f"the program has no {k}; it runs {v}")
    cfg = ModelConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        tie_embeddings=c["tie_word_embeddings"], rope_theta=c["rope_theta"],
        act=c["hidden_act"], norm_eps=c["rms_norm_eps"], dtype=c["torch_dtype"],
        source=c["source"],
    )
    if cfg.padded_vocab != c["padded_vocab_size"]:
        raise ValueError(f"the program pads the vocabulary to {cfg.padded_vocab}, "
                         f"the file says {c['padded_vocab_size']}")
    return cfg


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
