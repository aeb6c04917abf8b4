"""CPU tests of the chip benchmark's yardstick: FLOP and byte counts, the
trace reduction, the traffic generator, the rate and latency arithmetic,
the device check and the shape of BENCHMARK.json."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import model_flops  # noqa: E402
import serve_driver  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402

TINY = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 2, "intermediate_size": 16,
        "padded_vocab_size": 32}


def test_flops_and_bytes_against_hand_counts():
    # per block: q 8x4x2=64, k 8x2x2=32, v 32, o 64 -> 192; mlp 3x8x16=384
    assert model_flops.layer_params(TINY) == 576
    # 2 blocks of 576 + 2 norms of 8, embedding 32x8, final norm 8
    assert model_flops.param_count(TINY) == 2 * (576 + 16) + 256 + 8
    # one generated token, 5 keys: 2x576 per block x2; attention: per
    # layer 4 heads x 2 dims x 5 keys x 2 products x 2 flops = 160, x2
    # layers; head 2x32x8
    assert model_flops.decode_flops(TINY, 5) == 2304 + 2 * 160 + 512
    # a 3-token prompt: 3 tokens through the blocks, 1+2+3 keys, one head
    assert model_flops.prompt_flops(TINY, 3) == 3 * 2304 + 2 * 4 * 4 * 2 * 6 + 512
    # kv: 2 layers x (k, v) x 2 heads x 2 dims x 2 bytes
    assert model_flops.kv_bytes_per_token(TINY) == 32
    params = 2 * model_flops.param_count(TINY)
    assert model_flops.decode_step_bytes(TINY, [4, 9]) == params + 32 * (5 + 10)
    # forward: (2304 + 512) x 4 tokens + attention over 1+2+3+4 keys; x3
    fwd = 2816 * 4 + 2 * 4 * 4 * 2 * 10
    assert model_flops.train_flops_per_sequence(TINY, 4) == 3 * fwd


def _synthetic():
    spans = [("bench.window", 0, 1000), ("bench.step", 100, 400),
             ("bench.record", 400, 450), ("bench.step", 500, 900)]
    ops = [("fusion.1", 100, 300), ("all-gather.2", 250, 350),
           ("fusion.3", 600, 800), ("all-reduce.4", 820, 860)]
    mods = [("jit_decode_step(7)", 100, 360), ("jit_train(8)", 590, 870)]
    return spans, [(ops, mods)]


def test_trace_reduction_synthetic():
    r = trace_reduce.reduce(*_synthetic(), offset=0.0)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100,350] + [600,800] + [820,860]
    assert r["busy_s"] == pytest.approx(490e-9)
    assert r["programs"]["decode_step"] == {"count": 1, "seconds": pytest.approx(260e-9)}
    assert r["programs"]["train"]["count"] == 1
    names = dict((k, v) for k, v in r["device_ops"])
    assert names["decode_step:fusion.1"] == pytest.approx(200e-9)
    assert names["train:all-reduce.4"] == pytest.approx(40e-9)
    # gaps: [0,100] window, [350,600] -> mid 475 in bench.step? no: record
    # ends 450, step starts 500 -> window; [800,820] step; [860,1000] window
    gaps = {(n, round(s * 1e9)) for n, s in r["idle_gaps"]}
    assert gaps == {("bench.window", 100), ("bench.window", 250),
                    ("bench.step", 20), ("bench.window", 140)}
    # collectives 100 + 40 ns; exposed: all-gather's [300,350] and all of
    # the all-reduce
    assert r["collective_s"] == pytest.approx(140e-9)
    assert r["exposed_collective_s"] == pytest.approx(90e-9)
    st = r["spans"]["bench.step"]
    assert st["count"] == 2 and st["seconds"] == pytest.approx(700e-9)
    assert r["clock_offset_s"] == 0.0


def test_clock_offset_puts_device_work_before_the_host_sees_it():
    # device work ends 1.5 ms (device clock) before each host step ends
    busy = [[0, 400_000], [3_000_000, 3_400_000], [7_000_000, 7_600_000]]
    ends = [1_950_000, 4_950_000, 9_100_000]
    d = trace_reduce.clock_offset(busy, ends)
    assert 1_000_000 <= d <= 1_550_000
    assert trace_reduce.op_name("%fusion.3 = bf16[2]{0} fusion(%p)") == "fusion.3"


def test_trace_reduction_needs_a_window():
    spans, dev = _synthetic()
    with pytest.raises(ValueError):
        trace_reduce.reduce(spans[1:], dev, offset=0.0)


def test_trace_reduction_on_a_recorded_chip_trace():
    path = HERE / "testdata" / "small_trace.xplane.pb"
    r = trace_reduce.reduce(*trace_reduce.load(str(path)))
    assert r["chips"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["spans"]["bench.step"]["count"] == 3
    assert sum(p["count"] for p in r["programs"].values()) >= 3
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    assert r["idle_gaps"][0][1] >= r["idle_gaps"][-1][1] > 0
    # the device clock of this trace runs ~1.5-2.4 ms behind the host's
    assert 1.5e-3 < r["clock_offset_s"] < 2.4e-3


def test_traffic_same_sizes_for_every_seed():
    mix = json.loads((HERE / "traffic" / "chat.json").read_text())
    a = traffic_gen.serve_requests(mix, 1, 128, 1000)
    b = traffic_gen.serve_requests(mix, 2**33 + 5, 128, 1000)
    for key in ("max_new",):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
        assert [r[key] for r in a] != [r[key] for r in b]
    assert sorted(len(r["tokens"]) for r in a) == sorted(len(r["tokens"]) for r in b)
    gaps = lambda rs: sorted(np.diff([0.0] + [r["due_s"] for r in rs]).round(9))
    assert gaps(a) == gaps(b)
    again = traffic_gen.serve_requests(mix, 1, 128, 1000)
    assert all((x["tokens"] == y["tokens"]).all() for x, y in zip(a, again))
    lens = [len(r["tokens"]) for r in a]
    assert min(lens) >= mix["prompt_len"]["min"] and max(lens) <= mix["prompt_len"]["max"]


def test_rate_credits_requests_cut_by_the_window():
    class H:
        def __init__(self, n):
            self.generated = [0] * n

    # a 2-second window; one request admitted before it ends and still
    # running (3 tokens in the window), one finished inside it
    recs = [
        {"h": H(3), "due": 1.5, "sent": 1.5, "times": [1.6, 1.7, 1.9]},
        {"h": H(2), "due": 0.1, "sent": 0.1, "times": [0.3, 0.5], "finished": 0.5},
    ]
    steps = [
        {"t0": 0.1, "t1": 0.3, "prompts": [10], "contexts": [], "tokens": 1},
        {"t0": 0.3, "t1": 0.5, "prompts": [], "contexts": [11], "tokens": 1},
        {"t0": 1.5, "t1": 1.6, "prompts": [20], "contexts": [], "tokens": 1},
        {"t0": 1.6, "t1": 1.7, "prompts": [], "contexts": [21], "tokens": 1},
        {"t0": 1.8, "t1": 1.9, "prompts": [], "contexts": [22], "tokens": 1},
    ]
    win = {"seconds": 2.0, "requests": recs, "steps": steps, "due": 3}
    e2e = serve_driver.end_to_end({"window": win}, {})
    assert e2e["serve_tokens_per_s"] == pytest.approx((30 + 5) / 2.0)
    assert e2e["output_tokens_per_s"] == pytest.approx(5 / 2.0)
    # the third request was due but never sent: it counts as unserved
    assert e2e["_counts"]["unserved"] == 1
    assert e2e["ttft_p90_s"] == float("inf")
    assert e2e["itl_p99_s"] == pytest.approx(0.2)
    assert e2e["itl_p98_s"] == pytest.approx(0.2)


def test_peaks_refuse_an_unknown_device():
    import chip_peaks

    assert chip_peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        chip_peaks.peaks_for("TPU v9 imaginary")


def _run(cwd: Path, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, env=env, timeout=300)


def test_refuses_a_run_without_an_accelerator():
    p = _run(ROOT, "--workload", "phi4_serve_chat", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode == 3
    assert "{" not in p.stdout


def test_refuses_to_run_from_the_benchmark_alone(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "--workload", "granite_train_2k", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert "setup_s" in e2e
    for w in cells.values():
        assert (ROOT / configs[w["config"]]["file"]).is_file()
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert (HERE / "limits" / f"{w['name']}.json").is_file()
        reported = [m for m in e2e.values() if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2
    for m in bench["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    for w in cells:
        assert any(w in m["workloads"] for m in bench["per_layer"])


@pytest.mark.parametrize("cell", [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_recorded_readings_against_the_committed_limits(cell):
    """The chip readings each limit was set from, judged by the committed
    limits: every sound run of the program is correct, and the control
    and every planted fault are not."""

    import output_check

    limits = json.loads((HERE / "limits" / f"{cell}.json").read_text())
    rec = json.loads((HERE / "readings" / f"{cell}.json").read_text())
    assert len(rec["program"]) >= 12
    for r in rec["program"]:
        assert output_check.judge(r, limits)[0], r
    assert len(rec["control"]) >= 3
    for r in rec["control"]:
        assert not output_check.judge(r, limits)[0], r
    for name, runs in rec["faults"].items():
        assert len(runs) >= 3, name
        for r in runs:
            assert not output_check.judge(r, limits)[0], (name, r)
    for name, r in rec.get("by_construction", {}).items():
        assert not output_check.judge(r, limits)[0], (name, r)
