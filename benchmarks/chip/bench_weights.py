"""Weights from the seed, shared by the program and the plain reference.

Each weight is drawn from its own key, ``fold_in(fold_in(seed_key, name),
layer)``, in float32 and rounded to bfloat16, the type it is served in.  The
program gets them in its own tree layout, all in one jitted call (its
``init`` entry point is swapped for :func:`program_init` while the system
under test is built).  The reference draws the same values one layer at a
time, so it never holds the whole model in float32.

Norm weights are drawn as ``delta`` around one: the reference scales by
``1 + delta``, the program stores ``delta`` (its norms keep ``scale - 1``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import zlib

import jax
import jax.numpy as jnp

EMBED_STD = 0.02
NORM_STD = 0.02
LAYER_KEYS = ("ln_attn", "wq", "wk", "wv", "wo", "ln_mlp", "w_gate", "w_up", "w_down")
NORMS = ("ln_attn", "ln_mlp", "final_norm")


def weight_seed(seed: int) -> int:
    """The 31-bit seed handed to the program as its own seed; its init
    turns it into ``PRNGKey(weight_seed)``, the key of :func:`seed_key`."""

    return int(seed) % 2**31


def seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(weight_seed(seed))


def shapes(c: dict) -> dict:
    d, H, Hk, dh, f = (c[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size"))
    return {
        "ln_attn": (d,), "wq": (d, H, dh), "wk": (d, Hk, dh), "wv": (d, Hk, dh),
        "wo": (H, dh, d), "ln_mlp": (d,), "w_gate": (d, f), "w_up": (d, f),
        "w_down": (f, d), "embed": (c["padded_vocab_size"], d), "final_norm": (d,),
    }


def std(c: dict, name: str) -> float:
    if name in NORMS:
        return NORM_STD
    if name == "embed":
        return EMBED_STD
    fan_in = {
        "wo": c["num_attention_heads"] * c["head_dim"],
        "w_down": c["intermediate_size"],
    }.get(name, c["hidden_size"])
    return 1.0 / math.sqrt(fan_in)


def draw(key, c: dict, name: str, layer, shape=None) -> jax.Array:
    """One weight as served (bfloat16); norms as their ``delta``."""

    shape = shapes(c)[name] if shape is None else shape
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(k, layer)
    x = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * std(c, name)
    return x.astype(jnp.bfloat16)


def program_init(shape_tree, c: dict):
    """An ``init(key)`` for the program: its own tree of shapes, every leaf
    filled by :func:`draw` from the key it is given.  Stacked layer leaves
    (under ``layers``) draw each layer by its index."""

    def init(key):
        def leaf(path, s):
            names = [getattr(p, "key", None) for p in path]
            name = names[-1]
            if name not in LAYER_KEYS and name not in ("embed", "final_norm"):
                raise KeyError(f"the program holds a weight the benchmark "
                               f"does not know: {jax.tree_util.keystr(path)}")
            if names[0] == "layers":
                x = jax.vmap(lambda i: draw(key, c, name, i, s.shape[1:]))(
                    jnp.arange(s.shape[0]))
            else:
                x = draw(key, c, name, 0, s.shape)
            return x.astype(s.dtype)

        return jax.tree_util.tree_map_with_path(leaf, shape_tree)

    return init


@contextlib.contextmanager
def program_weights(c: dict):
    """While open, every model the program builds draws its weights with
    :func:`program_init` (the program's own init, shapes and shardings stay
    as they are; only the values come from the benchmark)."""

    from repro.models import api

    original = api.build

    def build(cfg):
        bundle = original(cfg)
        tree = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
        return dataclasses.replace(bundle, init=program_init(tree, c))

    api.build = build
    try:
        yield
    finally:
        api.build = original
