"""CPU tests of ``program_spans.py``: the program's ``repro.*`` spans read
beside the device's work, on a synthetic trace and on traces recorded on a
TPU v5e."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import program_spans  # noqa: E402
import trace_reduce  # noqa: E402

SPANS = HERE / "testdata" / "small_trace_spans.xplane.pb"


def _synthetic():
    spans = [("bench.window", 0, 1000), ("bench.step", 100, 400),
             ("bench.record", 400, 450), ("bench.step", 500, 900)]
    ops = [("fusion.1", 100, 300), ("all-gather.2", 250, 350),
           ("fusion.3", 600, 800), ("all-reduce.4", 820, 860)]
    mods = [("jit_decode_step(7)", 100, 360), ("jit_train(8)", 590, 870)]
    return spans, [(ops, mods)]


ADMIT = {"rows": 3, "padded_rows": 4, "length": 8, "real_tokens": 12}
PROGRAM = [
    ("repro.engine.step", 500, 900, {"step": 0}, "main"),
    ("repro.request.start", 560, 580, {"name": "decode_step"}, "main"),
    ("repro.engine.admit", 510, 550, ADMIT, "main"),
    ("repro.engine.first_token", 520, 540, {"rows": 3}, "main"),
    ("repro.engine.grow", 790, 830, {"preempted": 0}, "main"),
    ("repro.engine.wait", 840, 890, {}, "main"),
    ("repro.engine.step", 950, 1100, {"step": 1}, "main"),   # cut by the window
    ("repro.engine.step", 1200, 1300, {"step": 2}, "main"),  # after it
    ("repro.engine.grow", 100, 200, {}, "io"),               # another thread
]


def test_without_program_spans_the_gaps_read_as_the_benchmark_names_them():
    spans, dev = _synthetic()
    r = program_spans.reduce(spans, dev, [], offset=0.0)
    base = trace_reduce.reduce(spans, dev, offset=0.0)
    assert r["idle_gaps"] == base["idle_gaps"]
    assert r["busy_s"] == pytest.approx(base["busy_s"])
    assert r["window_s"] == base["window_s"]
    assert (r["spans"], r["list"], r["idle_share"], r["metrics"]) == ({}, [], {}, {})
    assert r["clock"]["causal"] is None


def test_program_spans_name_the_gaps_they_hold():
    spans, dev = _synthetic()
    r = program_spans.reduce(spans, dev, PROGRAM, offset=0.0)
    # the gap [800, 820] lies in the grow span, inside bench.step; the rest
    # keep their names
    gaps = {(n, round(s * 1e9)) for n, s in r["idle_gaps"]}
    assert gaps == {("bench.window", 100), ("bench.window", 250),
                    ("repro.engine.grow", 20), ("bench.window", 140)}
    assert r["idle_by_span"] == pytest.approx({"bench.window": 490e-9,
                                               "repro.engine.grow": 20e-9})
    st = r["spans"]
    assert st["repro.engine.step"]["count"] == 2
    assert st["repro.engine.step"]["seconds"] == pytest.approx(450e-9)
    # less the admit, the dispatch, the grow and the wait nested in step 0
    assert st["repro.engine.step"]["self_seconds"] == pytest.approx((400 - 150 + 50) * 1e-9)
    assert st["repro.engine.admit"]["self_seconds"] == pytest.approx(20e-9)
    assert st["repro.engine.grow"]["count"] == 2
    lst = r["list"]
    parent = {sp["name"] + str(sp["stats"]): sp["parent"] for sp in lst}
    assert lst[parent["repro.engine.first_token{'rows': 3}"]]["name"] == "repro.engine.admit"
    assert parent["repro.engine.grow{}"] is None
    # idle inside the steps: [500, 900] less busy [600, 800] and [820, 860],
    # and [950, 1000] all idle; inside the grow spans [790, 830] 20 and the
    # other thread's [100, 200] none
    assert r["idle_share"]["repro.engine.step"] == pytest.approx((160 + 50) / 1000)
    assert r["idle_share"]["repro.engine.grow"] == pytest.approx(20 / 1000)
    assert program_spans.less_nested(lst, "repro.engine.step", (
        "repro.engine.wait", "repro.engine.first_token")) == pytest.approx([330e-9, 50e-9])
    m = r["metrics"]
    assert m["sched_ms_per_step"] == pytest.approx(1e3 * 190e-9)
    assert m["step_idle"] == pytest.approx(21.0)
    assert m["dispatch_us"] == pytest.approx(20e-3)
    assert m["prefill_useful"] == pytest.approx(100 * 12 / 32)
    assert "trainer_host_ms_per_step" not in m


def test_trainer_spans_give_the_host_time_outside_the_wait():
    spans, dev = _synthetic()
    program = [("repro.trainer.step", 100, 450, {"step": 0}, "main"),
               ("repro.trainer.batch", 100, 120, {}, "main"),
               ("repro.request.start", 120, 140, {"name": "step_fn"}, "main"),
               ("repro.trainer.wait", 140, 370, {}, "main"),
               ("repro.trainer.record", 370, 440, {}, "main")]
    r = program_spans.reduce(spans, dev, program, offset=0.0)
    assert r["metrics"] == pytest.approx({"dispatch_us": 20e-3,
                                          "trainer_host_ms_per_step": 120e-6})


def test_causality_brackets_the_clock_offset():
    # two decode steps: each dispatched 100 ns before it runs on the device
    # (device clock 1000 ns behind), its wait ending 50 ns after it ends
    mods = [("jit_decode_step(3)", 0, 300), ("jit_decode_step(3)", 1000, 1300)]
    program = [("repro.request.start", 900, 910, {"name": "decode_step"}, "m"),
               ("repro.engine.wait", 950, 1350, {}, "m"),
               ("repro.request.start", 1900, 1920, {"name": "decode_step"}, "m"),
               ("repro.engine.wait", 1950, 2350, {}, "m")]
    b = program_spans.clock_bracket(program, mods, 1000)
    assert b == {"low_s": pytest.approx(900e-9), "high_s": pytest.approx(1050e-9),
                 "pairs": 2, "inside": True}
    assert not program_spans.clock_bracket(program, mods, 1100)["inside"]
    # a dispatch without its execution in the trace: no pairing
    assert program_spans.clock_bracket(program[:2] + program[:1], mods[:1], 1000) is None


def test_recorded_chip_trace_with_program_spans():
    """A traced chat window of 54 engine steps (phi4_serve_chat on a TPU
    v5e, seed 3400000101, 4 s), cut to what the reductions read: the
    host's ``bench.*`` and ``repro.*`` spans with their stats, the device's
    ``XLA Modules``, and its ``XLA Ops`` merged into one ``busy`` event per
    stretch of back-to-back operations."""

    spans, devices = trace_reduce.load(str(SPANS))
    program = program_spans.load(str(SPANS))
    r = program_spans.reduce(spans, devices, program)
    base = trace_reduce.reduce(spans, devices)
    assert r["spans"]["repro.engine.step"]["count"] == 54
    m = r["metrics"]
    # one dispatch well under a millisecond, the scheduler's host time
    # under a decode step, the idle share inside steps under the window's
    decode_ms = 1e3 * base["programs"]["decode_step"]["seconds"] / \
        base["programs"]["decode_step"]["count"]
    assert 10 < m["dispatch_us"] < 1000
    assert 0.1 < m["sched_ms_per_step"] < decode_ms
    assert 0 < m["step_idle"] < 100 * (1 - r["busy_s"] / r["window_s"])
    assert 0 < m["prefill_useful"] < 100
    # every idle gap inside an engine step is named by a program span
    assert all(n.startswith("repro.") for n, _ in r["idle_gaps"][1:])
    # one clock: causality (a program runs after its dispatch starts, and
    # the host's wait for it ends after it ends) allows the fitted offset,
    # and the benchmark's own, within a bracket under 2 ms wide
    c = r["clock"]
    assert c["causal"]["pairs"] == 54 and c["causal"]["inside"]
    assert c["causal"]["low_s"] <= c["bench_step_offset_s"] <= c["causal"]["high_s"]
    assert c["causal"]["high_s"] - c["causal"]["low_s"] < 2e-3
    # each wait ends within 1 ms after the end of the last device program
    # that started before it (the sample's)
    off = c["offset_s"] * 1e9
    mods = sorted((s + off, e + off) for _, s, e in devices[0][1])
    w0 = next(s for n, s, _ in spans if n == "bench.window")
    late = []
    for sp in r["list"]:
        if sp["name"] == "repro.engine.wait":
            end = w0 + (sp["start_s"] + sp["seconds"]) * 1e9
            last = max((m for m in mods if m[0] < end), key=lambda m: m[0])
            late.append(end - last[1])
    assert len(late) == 54
    assert sum(0 <= d < 1e6 for d in late) >= 0.95 * len(late)


def test_recorded_chip_trace_without_program_spans():
    path = str(HERE / "testdata" / "small_trace.xplane.pb")
    spans, devices = trace_reduce.load(path)
    r = program_spans.reduce(spans, devices, program_spans.load(path))
    base = trace_reduce.reduce(spans, devices)
    assert r["idle_gaps"] == base["idle_gaps"]
    assert r["clock"]["offset_s"] == r["clock"]["bench_step_offset_s"] == base["clock_offset_s"]
    assert r["metrics"] == {} and r["list"] == []


def test_the_command_reads_a_trace(tmp_path, capsys):
    out = tmp_path / "report.json"
    assert program_spans.main(["--xplane", str(SPANS), "--out", str(out)]) == 0
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    whole = json.loads(out.read_text())
    assert "list" not in printed and len(whole["list"]) > 54
    assert printed["metrics"] == whole["metrics"]
