"""Program spans (``tool.span``, the MPI_T events analogue): a tiny engine
run and a tiny trainer run inside a profiler session leave the registered
``repro.*`` spans in the trace, nested and with their stats; outside a
session a span records nothing."""

from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core import tool
from repro.launch.mesh import make_host_communicator, make_host_mesh
from repro.runtime.engine import Engine, EngineConfig
from repro.runtime.server import Server, ServerConfig
from repro.runtime.trainer import Trainer, TrainerConfig

BUCKET = 8


def _traced(tmp_path, fn):
    """Run ``fn`` in a profiler session; the ``repro.*`` host spans of the
    trace as dicts with ``name``, ``start``, ``end``, ``stats`` and the
    index of their ``parent`` (the innermost span of the same thread that
    holds them)."""

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    out = []
    for p, plane in enumerate(ProfileData.from_file(str(path)).planes):
        if not plane.name.startswith("/host:"):
            continue
        for t, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append({"name": ev.name, "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns,
                                "stats": dict(ev.stats), "thread": (p, t)})
    for sp in out:
        holders = [i for i, o in enumerate(out)
                   if o is not sp and o["thread"] == sp["thread"]
                   and o["start"] <= sp["start"] and sp["end"] <= o["end"]]
        sp["parent"] = max(holders, key=lambda i: out[i]["start"], default=None)
    return out


def _ancestors(spans, sp):
    names, i = [], sp["parent"]
    while i is not None:
        names.append(spans[i]["name"])
        i = spans[i]["parent"]
    return names


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_engine_spans_nest_and_carry_their_stats(tmp_path):
    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=32, num_heads=2,
                      num_kv_heads=1, head_dim=16, d_ff=64, vocab_size=64, dtype="float32")
    server = Server(cfg, ParallelConfig(),
                    ServerConfig(max_batch=4, max_new_tokens=4, temperature=0.0),
                    make_host_communicator())
    engine = Engine(server, EngineConfig(prompt_bucket=BUCKET, block_tokens=4))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 64, size=(n,), dtype=np.int32) for n in (3, 8, 5)]
    handles = [engine.submit(p, max_new=m) for p, m in zip(prompts, (4, 2, 3))]

    spans = _traced(tmp_path, engine.run)

    steps = _named(spans, "repro.engine.step")
    assert [s["stats"]["step"] for s in steps] == list(range(len(steps)))
    assert steps[0]["stats"] == {"step": 0, "running": 0, "waiting": 3}
    for name in ("repro.engine.admit", "repro.engine.first_token", "repro.engine.grow",
                 "repro.engine.sample", "repro.engine.wait", "repro.engine.retire",
                 "repro.engine.admit_row", "repro.engine.finish"):
        got = _named(spans, name)
        assert got, name
        assert all("repro.engine.step" in _ancestors(spans, s) for s in got), name
    assert len(_named(spans, "repro.engine.wait")) == len(steps)
    assert all(s["parent"] is not None and spans[s["parent"]]["name"] == "repro.engine.step"
               for s in _named(spans, "repro.engine.wait"))

    # one side batch of three rows, padded to four, at the bucket's length
    (admit,) = _named(spans, "repro.engine.admit")
    assert admit["stats"] == {"rows": 3, "padded_rows": 4, "length": BUCKET,
                              "real_tokens": sum(len(p) for p in prompts)}
    assert _named(spans, "repro.engine.first_token")[0]["stats"] == {"rows": 3}
    rids = sorted(h.rid for h in handles)
    assert sorted(s["stats"]["rid"] for s in _named(spans, "repro.engine.admit_row")) == rids
    finish = {s["stats"]["rid"]: s["stats"]["tokens"] for s in _named(spans, "repro.engine.finish")}
    assert finish == {h.rid: len(h.generated) for h in handles}
    assert sum(s["stats"]["retired"] for s in _named(spans, "repro.engine.retire")) == 3
    assert all(s["stats"]["preempted"] == 0 for s in _named(spans, "repro.engine.grow"))

    starts = _named(spans, "repro.request.start")
    by_name = {}
    for s in starts:
        by_name.setdefault(s["stats"]["name"], []).append(_ancestors(spans, s))
    assert len(by_name["decode_step"]) == len(_named(spans, "repro.engine.sample"))
    assert all(a[0] == "repro.engine.step" for a in by_name["decode_step"])
    assert all(a[0] == "repro.engine.admit" for a in by_name["prefill_step"])
    assert all(a[0] == "repro.engine.admit_row" for a in by_name["insert_step"])


def test_trainer_spans_nest_and_carry_their_stats(tmp_path):
    cfg = ModelConfig(name="tiny", family="dense", num_layers=1, d_model=32, num_heads=2,
                      num_kv_heads=1, head_dim=16, d_ff=64, vocab_size=64)
    trainer = Trainer(cfg, ParallelConfig(), TrainerConfig(steps=3, log_every=2),
                      make_host_mesh(), seq_len=16, global_batch=2, clock=lambda: 0.0)

    spans = _traced(tmp_path, trainer.run)

    steps = _named(spans, "repro.trainer.step")
    assert [s["stats"] for s in steps] == [{"step": 0}, {"step": 1}, {"step": 2}]
    for name, count in (("repro.trainer.batch", 3), ("repro.trainer.wait", 3),
                        ("repro.trainer.record", 2)):   # steps 2 and 3 (the last) log
        got = _named(spans, name)
        assert len(got) == count, name
        assert all(spans[s["parent"]]["name"] == "repro.trainer.step" for s in got), name
    fired = [s for s in _named(spans, "repro.request.start")
             if s["stats"]["name"] == "step_fn"]
    assert len(fired) == 3
    assert all(spans[s["parent"]]["name"] == "repro.trainer.step" for s in fired)


def test_span_registry_and_no_session():
    names = tool.span_info()
    for n in ("repro.request.start", "repro.engine.step", "repro.engine.admit",
              "repro.engine.admit_row", "repro.engine.first_token", "repro.engine.grow",
              "repro.engine.sample", "repro.engine.wait", "repro.engine.retire",
              "repro.engine.finish", "repro.trainer.step", "repro.trainer.batch",
              "repro.trainer.wait", "repro.trainer.record"):
        assert names.get(n), n
    tool.span_register("repro.test.span", "registered by a test")
    assert tool.span_info()["repro.test.span"] == "registered by a test"
    # no profiler session: the span records nothing and takes stats quietly
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with tool.span("repro.test.span", rid=1) as sp:
        sp.set_metadata(rows=2)
    with pytest.raises(KeyError):
        with tool.span("repro.test.span"):
            raise KeyError("propagates")
