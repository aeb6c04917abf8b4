"""Operations and bytes of a dense GQA decoder, computed from the shapes in
a configuration file.  Model FLOPs only: no recomputation, no padding, and
the causal half of attention.  A multiply-add is two FLOPs."""

from __future__ import annotations


def dims(c: dict) -> dict:
    return {
        "L": c["num_hidden_layers"],
        "d": c["hidden_size"],
        "H": c["num_attention_heads"],
        "Hk": c["num_key_value_heads"],
        "dh": c["head_dim"],
        "f": c["intermediate_size"],
        "V": c["padded_vocab_size"],
    }


def layer_params(c: dict) -> int:
    """Weights of one block that take part in matrix products."""

    g = dims(c)
    attn = g["d"] * g["dh"] * (2 * g["H"] + 2 * g["Hk"])
    return attn + 3 * g["d"] * g["f"]


def param_count(c: dict) -> int:
    """Every parameter, the tied embedding once, the norms included."""

    g = dims(c)
    return g["L"] * (layer_params(c) + 2 * g["d"]) + g["V"] * g["d"] + g["d"]


def param_bytes(c: dict, itemsize: int = 2) -> int:
    return param_count(c) * itemsize


def attention_flops(c: dict, keys: int) -> int:
    """Scores and weighted values of one query token against ``keys`` keys,
    over every layer."""

    g = dims(c)
    return g["L"] * 4 * g["H"] * g["dh"] * keys


def head_flops(c: dict) -> int:
    g = dims(c)
    return 2 * g["V"] * g["d"]


def prompt_flops(c: dict, prompt_len: int) -> int:
    """Forward FLOPs of one prompt: every token through the blocks, its own
    causal context in attention, and the head for the last token only."""

    n = prompt_len
    return (
        2 * layer_params(c) * dims(c)["L"] * n
        + attention_flops(c, n * (n + 1) // 2)
        + head_flops(c)
    )


def decode_flops(c: dict, context: int) -> int:
    """Forward FLOPs of one generated token attending to ``context`` keys."""

    return 2 * layer_params(c) * dims(c)["L"] + attention_flops(c, context) + head_flops(c)


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    g = dims(c)
    return g["L"] * 2 * g["Hk"] * g["dh"] * itemsize


def decode_step_bytes(c: dict, contexts: list[int], itemsize: int = 2) -> int:
    """Least HBM traffic of one decode step: every parameter once, the keys
    and values each row attends to, and the new key and value it writes."""

    kv = kv_bytes_per_token(c, itemsize)
    return param_bytes(c, itemsize) + sum(kv * (n + 1) for n in contexts)


def train_flops_per_sequence(c: dict, seq: int) -> int:
    """Forward and backward (three forwards' worth) of one training sequence,
    the head over every position."""

    fwd = (
        (2 * layer_params(c) * dims(c)["L"] + head_flops(c)) * seq
        + attention_flops(c, seq * (seq + 1) // 2)
    )
    return 3 * fwd
