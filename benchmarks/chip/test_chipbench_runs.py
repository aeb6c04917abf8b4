"""CPU runs of the chip benchmark at a tiny size, past its look for a chip:
a sound run of each kind of cell is correct and compiles, traces and
preempts nothing in its window; with the timed path broken underneath,
``correct`` comes out false."""

from __future__ import annotations

import copy
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import harness  # noqa: E402
import run as bench_run  # noqa: E402

TINY = {
    "name": "tiny", "source": "test", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 500, "padded_vocab_size": 512,
    "tie_word_embeddings": True, "rope_theta": 10000.0, "partial_rotary_factor": 1.0,
    "rope_scaling": None, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "torch_dtype": "bfloat16", "program": {"arch": "phi4_mini_3_8b"}, "family": "dense_gqa",
}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**33 + 17


def serve_spec(mix_name: str) -> dict:
    mix = json.loads((HERE / "traffic" / f"{mix_name}.json").read_text())
    mix.update(prompt_bucket=32, slots=4, block=16, check_tokens=64, check_requests=4,
               max_requests_per_s=400)
    mix["prompt_len"].update(median=12, min=4, max=32)
    mix["output_len"].update(median=6, min=2, max=16)
    if mix["load"] == "open_loop":
        mix["rate_per_s"] = 20.0
    limits = json.loads((HERE / "limits" / f"phi4_serve_{mix_name}.json").read_text())
    # a 64-wide model's logits spread several times less than the cell's:
    # a token altered reads 0.6-0.7 here (4.6-6.3 on the chip), sound runs
    # 0-0.002; its own limit between them
    limits["max_gap"] = {"limit": 0.3}
    return {"cell": {"chips": 1}, "config": TINY, "family": harness.family(TINY),
            "traffic": mix, "limits": limits, "end_to_end": [], "per_layer": []}


def train_spec() -> dict:
    job = json.loads((HERE / "traffic" / "train_2k.json").read_text())
    job.update(seq_len=32, batch=4, lr=1e-2)
    limits = json.loads((HERE / "limits" / "granite_train_2k.json").read_text())
    # a 64-wide model's bf16 gradients lie farther from float32 than the
    # cell's (sound runs here read ~1.4e-3): its own limit, between that and
    # the faults below
    limits["grad_gap"] = {"limit": 0.01}
    return {"cell": {"chips": 1}, "config": TINY, "family": harness.family(TINY),
            "traffic": job, "limits": limits, "end_to_end": [], "per_layer": []}


def cell(spec, fault=None, seconds=1.5):
    fresh = copy.deepcopy(spec, {id(spec["family"]): spec["family"]})   # modules stay shared
    return bench_run.run_cell(fresh, SEED, seconds, False, time.perf_counter(), CPU, fault)


def quiet_window(capsys) -> dict:
    lines = [json.loads(x[len("bench: "):]) for x in capsys.readouterr().out.splitlines()
             if x.startswith("bench: ")]
    return next(x["in_window"] for x in lines if "in_window" in x)


@pytest.mark.parametrize("mix", ["chat", "offline"])
def test_serve_cell_sound_run(mix, capsys):
    line = cell(serve_spec(mix))
    assert line["correct"], line
    assert line["metrics"] == {}          # a CPU run reports no device metric
    assert line["attempted"] > 0 and list(line)[-1] == "checks"
    counts = quiet_window(capsys)
    assert all(v == 0 for k, v in counts.items() if k != "cache_hits"), counts


def test_serve_cell_token_altered(capsys):
    def fault(server, engine):
        sample = server._sample
        server._sample = lambda logits, key: sample(logits, key).at[0].add(1) % 500

    line = cell(serve_spec("chat"), fault)
    assert not line["correct"], line


def test_train_cell_sound_run(capsys):
    line = cell(train_spec())
    assert line["correct"], line
    counts = quiet_window(capsys)
    assert all(v == 0 for k, v in counts.items() if k != "cache_hits"), counts


def test_train_cell_state_unchanged():
    def fault(trainer):
        from repro.optim import AdamW

        class Still(AdamW):
            def update(self, grads, state, params):
                return params, state

        trainer.opt = Still(**{f.name: getattr(trainer.opt, f.name)
                               for f in dataclasses.fields(AdamW)})

    line = cell(train_spec(), fault, seconds=0.5)
    assert not line["correct"], line


def test_train_cell_half_batch():
    def fault(trainer):
        draw = trainer.pipeline.device_batch

        def half(step, mesh=None, pcfg=None):
            t = draw(step, mesh, pcfg)["tokens"]
            h = t.shape[0] // 2
            return {"tokens": jnp.concatenate([t[:h], t[:h]])}

        trainer.pipeline.device_batch = half

    line = cell(train_spec(), fault, seconds=0.5)
    assert not line["correct"], line
