"""Weights from the seed, shared by the program and the plain reference.

Each weight is drawn from its own key, ``fold_in(fold_in(seed_key, name),
layer)``, in float32 and rounded to bfloat16, the type it is served in.
What a weight is called, its shape and its scale are its family's
(``families/<family>.py``): ``leaf(c, path)`` names each of the program's
leaves from its full path, and ``weights(c)`` gives each name's shape and
standard deviation.  The program gets them in its own tree layout, all in
one jitted call (its ``init`` entry point is swapped for
:func:`program_init` while the system under test is built).  The reference
draws the same values one layer at a time, so it never holds the whole
model in float32.

Norm weights are drawn as ``delta`` around one: the reference scales by
``1 + delta``, the program stores ``delta`` (its norms keep ``scale - 1``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import zlib
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    """What a family calls one of the program's leaves."""

    name: str                 # the weight's draw, and its key in the training check
    layer: int | None = None  # its layer, the first of a stacked leaf; None model-wide
    stacked: bool = False     # stacked over consecutive layers on its first axis


def weight_seed(seed: int) -> int:
    """The 31-bit seed handed to the program as its own seed; its init
    turns it into ``PRNGKey(weight_seed)``, the key of :func:`seed_key`."""

    return int(seed) % 2**31


def seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(weight_seed(seed))


def draw(key, name: str, layer, shape, std: float) -> jax.Array:
    """One weight as served (bfloat16); norms as their ``delta``."""

    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(k, layer)
    x = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * std
    return x.astype(jnp.bfloat16)


def leaf(fam, c: dict, path) -> Leaf:
    """The family's name for the program's leaf at ``path``; refused where
    the family names none."""

    lf = fam.leaf(c, tuple(getattr(p, "key", None) for p in path))
    if lf is None:
        raise KeyError(f"the program holds a weight the benchmark "
                       f"does not know: {jax.tree_util.keystr(path)}")
    return lf


def check_key(lf: Leaf, i: int = 0) -> str:
    """The training check's key of a leaf (of its ``i``-th stacked layer):
    ``name`` for a model-wide weight, else ``name.layer``."""

    return lf.name if lf.layer is None else f"{lf.name}.{lf.layer + i}"


def program_init(shape_tree, fam, c: dict):
    """An ``init(key)`` for the program: its own tree of shapes, every leaf
    filled by :func:`draw` from the key it is given, under the name, shape
    and scale its family gives it.  A stacked leaf draws each layer by its
    index; a model-wide one as layer 0."""

    spec = fam.weights(c)

    def init(key):
        def one(path, s):
            lf = leaf(fam, c, path)
            shape, std = spec[lf.name]
            held = s.shape[1:] if lf.stacked else s.shape
            if tuple(held) != tuple(shape):
                raise ValueError(f"the program holds {jax.tree_util.keystr(path)} as "
                                 f"{tuple(held)}, its family draws {lf.name} as {tuple(shape)}")
            if lf.stacked:
                x = jax.vmap(lambda i: draw(key, lf.name, lf.layer + i, shape, std))(
                    jnp.arange(s.shape[0]))
            else:
                x = draw(key, lf.name, lf.layer or 0, shape, std)
            return x.astype(s.dtype)

        return jax.tree_util.tree_map_with_path(one, shape_tree)

    return init


@contextlib.contextmanager
def program_weights(fam, c: dict):
    """While open, every model the program builds draws its weights with
    :func:`program_init` (the program's own init, shapes and shardings stay
    as they are; only the values come from the benchmark)."""

    from repro.models import api

    original = api.build

    def build(cfg):
        bundle = original(cfg)
        tree = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
        return dataclasses.replace(bundle, init=program_init(tree, fam, c))

    api.build = build
    try:
        yield
    finally:
        api.build = original
