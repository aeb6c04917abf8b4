"""Plain float32 reference of a dense GQA decoder, as its configuration file
states it: RMSNorm, rotary positions over ``partial_rotary_factor`` of each
head, grouped-query causal attention, a SwiGLU MLP, a tied embedding as the
head, and the softmax over every row of the (padded) embedding.

It imports nothing of the program.  Weights come from
:mod:`bench_weights` one layer at a time, under the names, shapes and
scales of :func:`weights`.  Every matrix product runs at
``Precision.HIGHEST``; with ``fp8=True`` the products of the projections,
the MLP and the head take float8 (e4m3) operands instead, which is the
control: the same model one precision step below bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import bench_weights as bw

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
EMBED_STD = 0.02
NORM_STD = 0.02
LAYER_KEYS = ("ln_attn", "wq", "wk", "wv", "wo", "ln_mlp", "w_gate", "w_up", "w_down")
NORMS = ("ln_attn", "ln_mlp", "final_norm")


def weights(c: dict) -> dict:
    """Each weight's shape (one layer's, for a layer weight) and standard
    deviation: ``{name: (shape, std)}``."""

    d, H, Hk, dh, f = (c[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size"))
    shapes = {
        "ln_attn": (d,), "wq": (d, H, dh), "wk": (d, Hk, dh), "wv": (d, Hk, dh),
        "wo": (H, dh, d), "ln_mlp": (d,), "w_gate": (d, f), "w_up": (d, f),
        "w_down": (f, d), "embed": (c["padded_vocab_size"], d), "final_norm": (d,),
    }
    fan_in = {"wo": H * dh, "w_down": f}
    return {n: (s, NORM_STD if n in NORMS else EMBED_STD if n == "embed"
                else 1.0 / math.sqrt(fan_in.get(n, d)))
            for n, s in shapes.items()}


def _draw(key, c: dict, name: str, layer):
    return bw.draw(key, name, layer, *weights(c)[name])


def _fp8(x, axis):
    """``x`` rounded to float8 (e4m3) under a scale per ``axis`` slice.  The
    rounding is the forward pass only: gradients pass straight through, as
    in float8 training, where the gradients themselves stay wider."""

    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x), axis=axis, keepdims=True)) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm(spec: str, x, w, fp8: bool):
    """A product of activations ``x`` (features last) and weights ``w``."""

    if fp8:
        x = _fp8(x, -1)
        w = _fp8(w, None)
    return jnp.einsum(spec, x, w, precision=HI)


def layer(key, c: dict, i) -> dict:
    """Layer ``i``'s weights in float32, norms as ``1 + delta``."""

    w = {n: _draw(key, c, n, i).astype(jnp.float32) for n in LAYER_KEYS}
    w["ln_attn"] = 1.0 + w["ln_attn"]
    w["ln_mlp"] = 1.0 + w["ln_mlp"]
    return w


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta: float, factor: float):
    """x: (B, T, heads, dh); rotates the first ``factor * dh`` features of
    each head in two halves."""

    T, dh = x.shape[1], x.shape[-1]
    rd = int(dh * factor)
    half = rd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rd], x[..., rd:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def attention(w: dict, x, c: dict, fp8: bool):
    """The attention half of a block, residual included."""

    eps = c["rms_norm_eps"]
    H, Hk, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    h = rms(x, w["ln_attn"], eps)
    q = mm("btd,dhk->bthk", h, w["wq"], fp8)
    k = mm("btd,dhk->bthk", h, w["wk"], fp8)
    v = mm("btd,dhk->bthk", h, w["wv"], fp8)
    q = rope(q, c["rope_theta"], c["partial_rotary_factor"])
    k = rope(k, c["rope_theta"], c["partial_rotary_factor"])
    k, v = jnp.repeat(k, H // Hk, axis=2), jnp.repeat(v, H // Hk, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) * c.get(
        "attention_multiplier", 1.0 / np.sqrt(dh))
    T = x.shape[1]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v, precision=HI)
    return x + c.get("residual_multiplier", 1.0) * mm("bthk,hkd->btd", a, w["wo"], fp8)


def swiglu(w: dict, h, fp8: bool):
    g = mm("btd,df->btf", h, w["w_gate"], fp8)
    u = mm("btd,df->btf", h, w["w_up"], fp8)
    return mm("btf,fd->btd", jax.nn.silu(g) * u, w["w_down"], fp8)


def block(w: dict, x, c: dict, fp8: bool):
    x = attention(w, x, c, fp8)
    h = rms(x, w["ln_mlp"], c["rms_norm_eps"])
    return x + c.get("residual_multiplier", 1.0) * swiglu(w, h, fp8)


def logits(x, embed, final_norm, c: dict, fp8: bool):
    lg = mm("...d,vd->...v", rms(x, final_norm, c["rms_norm_eps"]), embed, fp8)
    return lg / c.get("logits_scaling", 1.0)


def embed_tokens(embed, tokens, c: dict):
    return embed[tokens] * c.get("embedding_multiplier", 1.0)


@functools.partial(jax.jit, static_argnames=("c_items",))
def _embed_rows(key, tokens, c_items):
    c = dict(c_items)
    return embed_tokens(_draw(key, c, "embed", 0).astype(jnp.float32), tokens, c)


def _items(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items() if not isinstance(v, (dict, list))))


def hidden(seed: int, c: dict, seqs: np.ndarray, fp8: bool = False, chunk: int = 8):
    """Final hidden states (before the last norm) of ``seqs`` (n, T), a
    layer at a time: each layer's weights are drawn once and applied to
    blocks of ``chunk`` sequences (zero rows pad the last block, so every
    block has one shape)."""

    ci = _items(c)
    key = bw.seed_key(seed)
    n = len(seqs)
    pad = np.zeros(((-n) % chunk, seqs.shape[1]), seqs.dtype)
    full = np.concatenate([seqs, pad])
    xs = [_embed_rows(key, jnp.asarray(full[i:i + chunk]), ci)
          for i in range(0, len(full), chunk)]
    draw = jax.jit(lambda k, i: layer(k, dict(ci), i))
    step = jax.jit(lambda w, x: block(w, x, dict(ci), fp8))
    for i in range(c["num_hidden_layers"]):
        w = draw(key, i)
        xs = [step(w, x) for x in xs]
        del w
    return jnp.concatenate(xs)[:n]


@functools.partial(jax.jit, static_argnames=("c_items",))
def _head_weights(key, c_items):
    c = dict(c_items)
    e = _draw(key, c, "embed", 0).astype(jnp.float32)
    return e, 1.0 + _draw(key, c, "final_norm", 0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("c_items", "fp8"))
def _head(xs, e, fn, c_items, fp8):
    c = dict(c_items)
    return logits(xs, e, fn, c, fp8)[:, : c["vocab_size"]]


def head(seed: int, c: dict, xs, fp8: bool = False):
    """The logit rows (R, vocab) of hidden states ``xs`` (R, d)."""

    return _head(xs, *_head_weights(bw.seed_key(seed), _items(c)), _items(c), fp8)


def score(seed: int, c: dict, xs, candidates: dict, fp8: bool = False,
          chunk: int = 256) -> dict:
    """Rank the next token from hidden states ``xs`` (R, d).  Returns, per
    row, the largest logit (``best``), the token that has it (``top``), and
    the logit of each candidate token in ``candidates`` (name -> (R,)
    tokens)."""

    ci = _items(c)
    e, fn = _head_weights(bw.seed_key(seed), ci)
    out = {"best": [], "top": [], **{n: [] for n in candidates}}
    for s in range(0, len(xs), chunk):
        lg = _head(xs[s:s + chunk], e, fn, ci, fp8)
        out["best"].append(np.asarray(lg.max(-1)))
        out["top"].append(np.asarray(lg.argmax(-1)))
        for n, t in candidates.items():
            out[n].append(np.asarray(jnp.take_along_axis(
                lg, jnp.asarray(t[s:s + chunk])[:, None], 1)[:, 0]))
    return {k: np.concatenate(v) for k, v in out.items()}


# -- training -------------------------------------------------------------------


def _nest(flat: dict, c: dict) -> dict:
    """The model's weights from a flat dict keyed ``name`` or ``name.layer``."""

    return {
        "embed": flat["embed"], "final_norm": flat["final_norm"],
        "layers": [{n: flat[f"{n}.{i}"] for n in LAYER_KEYS}
                   for i in range(c["num_hidden_layers"])],
    }


def params(seed: int, c: dict) -> dict:
    """The whole model in float32, flat (training cells are cut to fit)."""

    key = bw.seed_key(seed)
    ci = _items(c)
    lay = jax.jit(lambda k, i: layer(k, dict(ci), i))
    out = {
        "embed": _draw(key, c, "embed", 0).astype(jnp.float32),
        "final_norm": 1.0 + _draw(key, c, "final_norm", 0).astype(jnp.float32),
    }
    for i in range(c["num_hidden_layers"]):
        out.update({f"{n}.{i}": w for n, w in lay(key, i).items()})
    return out


def row_loss(p: dict, tokens, c: dict, fp8: bool):
    """Mean next-token cross-entropy of rows (B, T)."""

    p = _nest(p, c)
    x = embed_tokens(p["embed"], tokens, c)
    for w in p["layers"]:
        x = jax.checkpoint(lambda w, x: block(w, x, c, fp8))(w, x)
    lg = logits(x[:, :-1], p["embed"], p["final_norm"], c, fp8)
    lz = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(lz - gold)


def lr_at(step: int, hp: dict) -> float:
    """The trainer's schedule: linear warm-up, then cosine to a tenth."""

    warm = min(1.0, (step + 1) / max(1, hp["warmup_steps"]))
    total = max(1, hp["total_steps"] - hp["warmup_steps"])
    progress = min(max((step - hp["warmup_steps"]) / total, 0.0), 1.0)
    cos = 0.1 + 0.9 * 0.5 * (1 + np.cos(np.pi * progress))
    return hp["lr"] * warm * cos


def train(seed: int, c: dict, batches: list, hp: dict, fp8: bool = False,
          rows_used: float = 1.0) -> dict:
    """Three AdamW steps on ``batches`` (each (B, T) tokens).  Returns the
    losses, the norms of the first gradient as the optimizer gets it (after
    clipping), and the norms of each weight's change over the steps.
    ``rows_used`` < 1 plants a fault: the mean over the first rows only.

    The weights and one gradient live on the device; the two moments live
    on the host and visit the device a weight at a time, so the reference
    fits beside nothing at the depth the program trains."""

    cc = dict(_items(c))
    return adamw(lambda: params(seed, c), lambda p, toks: row_loss(p, toks, cc, fp8),
                 batches, hp, rows_used)


def adamw(init, loss, batches: list, hp: dict, rows_used: float = 1.0) -> dict:
    """The trainer's AdamW over ``batches``, as :func:`train` describes it,
    for any model: ``init()`` gives its weights flat in float32 (it is
    called again for the change), ``loss(p, tokens)`` its mean loss."""

    p = init()
    m = {k: np.zeros(x.shape, np.float32) for k, x in p.items()}
    v = {k: np.zeros(x.shape, np.float32) for k, x in p.items()}
    grad = jax.jit(jax.value_and_grad(loss))
    norm = jax.jit(lambda g: jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))))

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def adam(p, g, m, v, scale, t, lr):
        g = g * scale
        m = hp["b1"] * m + (1 - hp["b1"]) * g
        v = hp["b2"] * v + (1 - hp["b2"]) * g * g
        bc1, bc2 = 1 - hp["b1"] ** t, 1 - hp["b2"] ** t
        p = p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + hp["eps"]) + hp["weight_decay"] * p)
        return p, m, v, jnp.linalg.norm(g.ravel())

    losses, first = [], None
    for t, toks in enumerate(batches, start=1):
        toks = np.asarray(toks)
        toks = toks[: max(1, int(round(len(toks) * rows_used)))]
        value, g = grad(p, jnp.asarray(toks))
        losses.append(float(value))
        scale = jnp.minimum(1.0, hp["clip_norm"] / jnp.maximum(norm(g), 1e-12))
        gn = {}
        for k in list(p):
            p[k], mk, vk, gn[k] = adam(p[k], g.pop(k), jnp.asarray(m[k]), jnp.asarray(v[k]),
                                      scale, float(t), lr_at(t, hp))
            m[k], v[k] = np.asarray(mk), np.asarray(vk)
        if first is None:
            first = {k: float(x) for k, x in gn.items()}
    start = init()
    change = {k: float(jnp.linalg.norm((p[k] - start.pop(k)).ravel())) for k in list(p)}
    return {"losses": losses, "grad_norms": first, "change_norms": change}
