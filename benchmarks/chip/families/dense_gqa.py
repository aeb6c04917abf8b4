"""The dense GQA decoder (phi4-mini, granite): RMSNorm, RoPE, grouped-query
attention, a SwiGLU MLP and a tied embedding as the head.

A family file is everything the harness knows about a model.  A
configuration file names its family (``"family": "<name>"``) and
``harness.load_spec`` loads ``families/<name>.py`` by path.  It gives:

- ``program_config(c)``: the program's ``ModelConfig`` for configuration
  file ``c``, refusing what the program cannot run as the file states it;
- ``weights(c)``: ``{name: (shape, std)}``, each weight's shape (one
  layer's, for a stacked weight) and standard deviation;
- ``leaf(c, path)``: the ``bench_weights.Leaf`` (name, layer, stacked) of
  the program's leaf at ``path``, the tuple of its keys; ``None`` for a
  leaf the family does not know, which the harness refuses;
- the plain reference: ``hidden(seed, c, seqs, fp8=False)``,
  ``head(seed, c, xs, fp8=False)``, ``score(seed, c, xs, candidates,
  fp8=False)`` and ``train(seed, c, batches, hp, fp8=False,
  rows_used=1.0)``, as :mod:`reference_lm` defines them; ``train`` keys its
  norms by :func:`bench_weights.check_key` of the leaves;
- the counts the metric readers take as ``ctx["flops"]``:
  ``decode_flops(c, context)``, ``decode_step_bytes(c, contexts)``,
  ``prompt_flops(c, prompt_len)`` and ``train_flops_per_sequence(c, seq)``.
"""

from __future__ import annotations

import math

import bench_weights as bw
import model_flops
import reference_lm

weights = reference_lm.weights
hidden = reference_lm.hidden
head = reference_lm.head
score = reference_lm.score
train = reference_lm.train

decode_flops = model_flops.decode_flops
decode_step_bytes = model_flops.decode_step_bytes
prompt_flops = model_flops.prompt_flops
train_flops_per_sequence = model_flops.train_flops_per_sequence

_PARENTS = {"ln_attn": (), "ln_mlp": (), "wq": ("attn",), "wk": ("attn",), "wv": ("attn",),
            "wo": ("attn",), "w_gate": ("mlp",), "w_up": ("mlp",), "w_down": ("mlp",)}
_LEAVES = {("embed",): bw.Leaf("embed"), ("final_norm",): bw.Leaf("final_norm"),
           **{("layers", "layer", *p, n): bw.Leaf(n, 0, True) for n, p in _PARENTS.items()}}


def leaf(c: dict, path: tuple) -> bw.Leaf | None:
    return _LEAVES.get(path)


def program_config(c: dict):
    """The program's ModelConfig for a configuration file.  Refuses what
    the program cannot run as the file states it."""

    from repro.configs.base import ModelConfig

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
