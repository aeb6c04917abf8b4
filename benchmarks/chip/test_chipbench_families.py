"""CPU tests of model families: a family the benchmark did not have arrives
as new files only and runs correct; the harness refuses a configuration
without a family file and a weight its family does not name; and the
dense GQA family draws, refers and configures as the harness did before
families (values pinned from that harness)."""

from __future__ import annotations

import collections
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import bench_weights as bw  # noqa: E402
import harness  # noqa: E402
import model_flops  # noqa: E402
import run as bench_run  # noqa: E402
from chip_peaks import PEAKS  # noqa: E402
from test_chipbench_runs import CPU, SEED, TINY, serve_spec, train_spec  # noqa: E402

# DeepSeekMoE's layout at a width of 64: one leading dense layer, then
# layers of 4 routed experts (each token to all 4) beside a shared expert,
# and an untied head
MOE = {
    "name": "tiny_gqa_moe", "source": "test", "family": "gqa_moe", "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32, "n_routed_experts": 4,
    "num_experts_per_tok": 4, "n_shared_experts": 1, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 500, "padded_vocab_size": 512,
    "tie_word_embeddings": False, "rope_theta": 10000.0, "partial_rotary_factor": 1.0,
    "rope_scaling": None, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "torch_dtype": "bfloat16", "program": {"arch": "grok_1_314b"},
}
CELLS = {"tiny_gqa_moe_chat": "tiny_chat", "tiny_gqa_moe_train": "tiny_train"}


def _files(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def _copy(dst: Path) -> Path:
    """The committed benchmark, copied: ``BENCHMARK.json`` and its files."""

    shutil.copy(ROOT / "BENCHMARK.json", dst)
    shutil.copytree(HERE, dst / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst / "benchmarks" / "chip"


@pytest.fixture(scope="module")
def moe_copy(tmp_path_factory):
    """A copy of the benchmark to which a family, a configuration, two
    mixes, their limits and two cells are added, and nothing else."""

    root = tmp_path_factory.mktemp("bench")
    here = _copy(root)
    shutil.copy(HERE / "testdata" / "family_gqa_moe.py", here / "families" / "gqa_moe.py")
    (here / "configs" / "tiny_gqa_moe.json").write_text(json.dumps(MOE))
    for cell, (mix, spec) in zip(CELLS, [("tiny_chat", serve_spec("chat")),
                                         ("tiny_train", train_spec())]):
        (here / "traffic" / f"{mix}.json").write_text(json.dumps(spec["traffic"]))
        (here / "limits" / f"{cell}.json").write_text(json.dumps(spec["limits"]))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": MOE["name"], "source": "test", "reduced": [],
                             "file": "benchmarks/chip/configs/tiny_gqa_moe.json",
                             "why": "a family the benchmark did not have"})
    bench["workloads"] += [{"name": cell, "config": MOE["name"], "traffic": mix, "chips": 1,
                            "why": "a family the benchmark did not have"}
                           for cell, mix in CELLS.items()]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))
    return root


def _spy(mod, names: list, calls: collections.Counter, seen: set) -> None:
    for name in names:
        fn = getattr(mod, name)

        def wrapped(*a, _fn=fn, _name=name, **kw):
            calls[_name] += 1
            out = _fn(*a, **kw)
            if _name == "leaf" and out is not None:
                seen.add(out.name)
            return out

        setattr(mod, name, wrapped)


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_family_arrives_as_new_files(cell, moe_copy):
    spec = harness.load_spec(cell, root=moe_copy)
    fam = spec["family"]
    assert Path(fam.__file__) == moe_copy / "benchmarks/chip/families/gqa_moe.py"
    calls, seen = collections.Counter(), set()
    _spy(fam, ["program_config", "weights", "leaf", "hidden", "score", "head", "train"],
         calls, seen)
    line = bench_run.run_cell(spec, SEED, 1.5, False, time.perf_counter(), CPU)
    assert line["correct"], line
    assert calls["program_config"] and calls["weights"]
    # an untied head and one last name (w_gate) under three parents:
    # dense_0/mlp, layers/layer/mlp and layers/layer/mlp/shared
    assert {"lm_head", "w_gate", "experts.w_gate", "shared.w_gate"} <= seen
    if cell.endswith("train"):
        assert calls["train"] == 1
    else:
        assert calls["hidden"] == 1 and calls["score"] and calls["head"]

    # what the copy holds beside the committed benchmark: new files, and
    # new entries in BENCHMARK.json
    before, after = _files(HERE), _files(moe_copy / "benchmarks" / "chip")
    assert {k: after[k] for k in before} == before
    assert {str(k) for k in set(after) - set(before)} == {
        "families/gqa_moe.py", "configs/tiny_gqa_moe.json", "traffic/tiny_chat.json",
        "traffic/tiny_train.json", "limits/tiny_gqa_moe_chat.json",
        "limits/tiny_gqa_moe_train.json"}
    bench = json.loads((moe_copy / "BENCHMARK.json").read_text())
    bench["configs"], bench["workloads"] = bench["configs"][:-1], bench["workloads"][:-2]
    assert bench == json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_readers_count_by_the_family(moe_copy):
    """``ctx["flops"]`` is the cell's family: the committed readers count a
    mixture of experts' work by its own file, not as a dense model."""

    spec = harness.load_spec("tiny_gqa_moe_chat", root=moe_copy)
    fam, c, pk = spec["family"], spec["config"], PEAKS["TPU v5 lite"]
    steps = [{"prompts": [7], "contexts": []}, {"prompts": [], "contexts": [8, 20]}]
    ctx = {"flops": fam, "config": c, "peaks": pk, "chips": 1, "traffic": {"seq_len": 32,
           "batch": 4}, "window": {"steps": steps, "seconds": 2.0},
           "trace": {"programs": {"decode_step": {"count": 1, "seconds": 1e-3}}}}
    flops = fam.prompt_flops(c, 7) + fam.decode_flops(c, 8) + fam.decode_flops(c, 20)
    assert bench_run.reader("mfu.chat")(ctx) == pytest.approx(
        100 * flops / (2.0 * pk["flops_bf16"]))
    least = max((fam.decode_flops(c, 8) + fam.decode_flops(c, 20)) / pk["flops_bf16"],
                fam.decode_step_bytes(c, [8, 20]) / pk["hbm_bytes_per_s"])
    assert bench_run.reader("decode_step_roofline.chat")(ctx) == pytest.approx(1e5 * least)
    ctx["window"] = {"steps": 3, "seconds": 2.0}
    assert bench_run.reader("mfu.train")(ctx) == pytest.approx(
        100 * fam.train_flops_per_sequence(c, 32) * 4 * 3 / (2.0 * pk["flops_bf16"]))
    # the dense counts would miss the routed experts and the untied head
    assert fam.decode_flops(c, 8) > model_flops.decode_flops(c, 8)
    assert fam.decode_step_bytes(c, [8]) > model_flops.decode_step_bytes(c, [8])


@pytest.mark.parametrize("family, error", [(None, ValueError),
                                           ("no_such_family", FileNotFoundError)])
def test_load_spec_refuses_a_config_without_its_family_file(family, error, tmp_path):
    here = _copy(tmp_path)
    path = here / "configs" / "phi4_mini_3_8b.json"
    c = json.loads(path.read_text())
    c.pop("family")
    if family is not None:
        c["family"] = family
    path.write_text(json.dumps(c))
    with pytest.raises(error):
        harness.load_spec("phi4_serve_chat", root=tmp_path)


def test_program_init_refuses_a_leaf_its_family_does_not_name():
    from repro.models import api

    untied = {**TINY, "tie_word_embeddings": False}
    fam = harness.family(untied)
    tree = jax.eval_shape(api.build(fam.program_config(untied)).init, jax.random.PRNGKey(0))
    assert "lm_head" in tree
    with pytest.raises(KeyError, match="lm_head"):
        jax.eval_shape(bw.program_init(tree, fam, untied), bw.seed_key(SEED))


# -- dense_gqa as the harness had it before families ----------------------------


def _model_config(**kw):
    from repro.configs.base import ModelConfig

    return ModelConfig(family="dense", tie_embeddings=True, rope_theta=10000.0, act="silu",
                       norm_eps=1e-05, dtype="bfloat16", num_kv_heads=8, head_dim=128, **kw)


PROGRAM_CONFIGS = {
    "phi4_mini_3_8b": dict(
        name="phi4_mini_3_8b", num_layers=32, d_model=3072, num_heads=24, d_ff=8192,
        vocab_size=200064, source="https://huggingface.co/microsoft/Phi-4-mini-instruct"),
    "granite_3_8b": dict(
        name="granite_3_8b", num_layers=4, d_model=4096, num_heads=32, d_ff=12800,
        vocab_size=49155, source="https://huggingface.co/ibm-granite/granite-3.0-8b-base"),
}

# per leaf of TINY drawn from SEED: (sum, sum of magnitudes), in float64
LEAVES = {
    "embed": (0.6189102791249752, 474.7888167537749),
    "final_norm": (-0.035639286041259766, 0.774625301361084),
    "layers/layer/attn/wk": (-10.979844376444817, 369.6117716282606),
    "layers/layer/attn/wo": (7.637067258358002, 734.8792586922646),
    "layers/layer/attn/wq": (8.158984661102295, 743.4352555274963),
    "layers/layer/attn/wv": (-2.2290616035461426, 379.392213344574),
    "layers/layer/ln_attn": (-0.07272619009017944, 1.8163595795631409),
    "layers/layer/ln_mlp": (0.03345811367034912, 1.8635097742080688),
    "layers/layer/mlp/w_down": (20.644896179437637, 1045.5291124284267),
    "layers/layer/mlp/w_gate": (-12.247329741716385, 1489.0050986111164),
    "layers/layer/mlp/w_up": (4.362436652183533, 1490.480385184288),
}
HIDDEN = (18.17699983145576, 2017.6585963292746)


def _sums(x) -> tuple:
    a = np.asarray(x, np.float64)
    return float(a.sum()), float(np.abs(a).sum())


def _pinned(got: tuple, want: tuple) -> bool:
    """Equal up to the last bit of a few values (1e-4 of the magnitudes):
    another draw or another model moves the sum by far more."""

    return abs(got[0] - want[0]) <= 1e-4 * want[1] and abs(got[1] - want[1]) <= 1e-4 * want[1]


@pytest.mark.parametrize("name", list(PROGRAM_CONFIGS))
def test_dense_gqa_program_config_as_before_families(name):
    c = json.loads((HERE / "configs" / f"{name}.json").read_text())
    got = harness.family(c).program_config(c)
    assert dataclasses.asdict(got) == dataclasses.asdict(_model_config(**PROGRAM_CONFIGS[name]))


def test_dense_gqa_draws_and_refers_as_before_families():
    from repro.models import api

    fam = harness.family(TINY)
    tree = jax.eval_shape(api.build(fam.program_config(TINY)).init, jax.random.PRNGKey(0))
    params = jax.jit(bw.program_init(tree, fam, TINY))(bw.seed_key(SEED))
    got = {"/".join(p.key for p in path): _sums(x.astype(np.float32))
           for path, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert set(got) == set(LEAVES)
    for k, want in LEAVES.items():
        assert _pinned(got[k], want), (k, got[k], want)
    seqs = ((np.arange(48).reshape(2, 24) * 37 + 5) % 500).astype(np.int32)
    assert _pinned(_sums(fam.hidden(SEED, TINY, seqs)), HIDDEN)
