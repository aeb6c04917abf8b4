"""A family for the tests: grouped-query attention with a mixture of
experts, laid out as DeepSeekMoE lays it out (leading dense layers, then
layers of routed experts beside a shared expert, and an untied head).
``test_chipbench_families.py`` installs it as ``families/gqa_moe.py`` in a
copy of the benchmark, to show that a family arrives as new files only.

It routes every token to every routed expert (``num_experts_per_tok`` =
``n_routed_experts``; anything else is refused), so the reference needs no
top-k and no expert's capacity is ever exceeded; the router's softmax
still weighs each expert's share.  The program's training loss adds the
router's load-balance loss (one a layer when every expert is chosen) and
z-loss, at the weights it gives them; the reference adds the same.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import bench_weights as bw
import model_flops
import reference_lm as ref
from reference_lm import HI, mm, rms

ATTN = ("ln_attn", "wq", "wk", "wv", "wo", "ln_mlp")
DENSE = (*ATTN, "w_gate", "w_up", "w_down")
MOE = (*ATTN, "router", "experts.w_gate", "experts.w_up", "experts.w_down",
       "shared.w_gate", "shared.w_up", "shared.w_down")
LOAD_BALANCE, Z_LOSS = 1e-2, 1e-3           # the program's weights of its router losses


def program_config(c: dict):
    from repro.configs.base import ModelConfig

    if c["num_experts_per_tok"] != c["n_routed_experts"]:
        raise ValueError("this family routes every token to every expert")
    return ModelConfig(
        name=c["name"], family="moe", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        tie_embeddings=c["tie_word_embeddings"], rope_theta=c["rope_theta"],
        act=c["hidden_act"], norm_eps=c["rms_norm_eps"], dtype=c["torch_dtype"],
        num_experts=c["n_routed_experts"], num_shared_experts=c["n_shared_experts"],
        moe_top_k=c["num_experts_per_tok"], moe_d_ff=c["moe_intermediate_size"],
        first_dense_layers=c["first_k_dense_replace"], source=c["source"],
    )


def weights(c: dict) -> dict:
    d, H, Hk, dh = (c[k] for k in ("hidden_size", "num_attention_heads",
                                   "num_key_value_heads", "head_dim"))
    f, fm, e = c["intermediate_size"], c["moe_intermediate_size"], c["n_routed_experts"]
    fs, V = c["n_shared_experts"] * fm, c["padded_vocab_size"]
    shapes = {
        "ln_attn": (d,), "wq": (d, H, dh), "wk": (d, Hk, dh), "wv": (d, Hk, dh),
        "wo": (H, dh, d), "ln_mlp": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
        "router": (d, e), "experts.w_gate": (e, d, fm), "experts.w_up": (e, d, fm),
        "experts.w_down": (e, fm, d), "shared.w_gate": (d, fs), "shared.w_up": (d, fs),
        "shared.w_down": (fs, d), "embed": (V, d), "final_norm": (d,), "lm_head": (d, V),
    }
    fan_in = {"wo": H * dh, "w_down": f, "experts.w_down": fm, "shared.w_down": fs}
    return {n: (s, ref.NORM_STD if n in ref.NORMS else ref.EMBED_STD if n == "embed"
                else 1.0 / math.sqrt(fan_in.get(n, d)))
            for n, s in shapes.items()}


_BLOCK = {("ln_attn",): "ln_attn", ("ln_mlp",): "ln_mlp",
          **{("attn", n): n for n in ("wq", "wk", "wv", "wo")}}
_DENSE = {**_BLOCK, **{("mlp", n): n for n in ("w_gate", "w_up", "w_down")}}
_MOE = {**_BLOCK, ("mlp", "router"): "router",
        **{("mlp", n): f"experts.{n}" for n in ("w_gate", "w_up", "w_down")},
        **{("mlp", "shared", n): f"shared.{n}" for n in ("w_gate", "w_up", "w_down")}}


def leaf(c: dict, path: tuple) -> bw.Leaf | None:
    head, rest = path[0], path[1:]
    if not rest and head in ("embed", "final_norm", "lm_head"):
        return bw.Leaf(head)
    if head == "layers" and rest[:1] == ("layer",) and rest[1:] in _MOE:
        return bw.Leaf(_MOE[rest[1:]], c["first_k_dense_replace"], True)
    first = c["first_k_dense_replace"]
    dense = {f"dense_{i}": i for i in range(first)}
    if head in dense and rest in _DENSE:
        return bw.Leaf(_DENSE[rest], dense[head])
    return None


# -- the plain reference ---------------------------------------------------------


def _names(c: dict, i: int) -> tuple:
    return DENSE if i < c["first_k_dense_replace"] else MOE


def _layer(key, c: dict, i: int) -> dict:
    spec = weights(c)
    w = {n: bw.draw(key, n, i, *spec[n]).astype(jnp.float32) for n in _names(c, i)}
    w["ln_attn"], w["ln_mlp"] = 1.0 + w["ln_attn"], 1.0 + w["ln_mlp"]
    return w


def _experts(w: dict, h, fp8: bool):
    """Every routed expert weighed by the router's softmax, plus the shared
    expert; and the router's z-loss over these tokens."""

    logits = jnp.einsum("btd,de->bte", h, w["router"], precision=HI)
    probs = jax.nn.softmax(logits, -1)
    g = mm("btd,edf->btef", h, w["experts.w_gate"], fp8)
    u = mm("btd,edf->btef", h, w["experts.w_up"], fp8)
    y = mm("btef,efd->bted", jax.nn.silu(g) * u, w["experts.w_down"], fp8)
    y = jnp.einsum("bte,bted->btd", probs, y, precision=HI)
    shared = {k: w[f"shared.{k}"] for k in ("w_gate", "w_up", "w_down")}
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return y + ref.swiglu(shared, h, fp8), z


def _block(w: dict, x, c: dict, fp8: bool):
    x = ref.attention(w, x, c, fp8)
    h = rms(x, w["ln_mlp"], c["rms_norm_eps"])
    if "router" not in w:
        return x + ref.swiglu(w, h, fp8), 0.0
    y, z = _experts(w, h, fp8)
    return x + y, z


def _items(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items() if not isinstance(v, (dict, list))))


def _logits(x, p: dict, c: dict, fp8: bool):
    return mm("...d,dv->...v", rms(x, p["final_norm"], c["rms_norm_eps"]), p["lm_head"], fp8)


@functools.partial(jax.jit, static_argnames=("ci",))
def _model_wide(key, ci):
    c, spec = dict(ci), weights(dict(ci))
    p = {n: bw.draw(key, n, 0, *spec[n]).astype(jnp.float32)
         for n in ("embed", "final_norm", "lm_head")}
    p["final_norm"] = 1.0 + p["final_norm"]
    return p


def hidden(seed: int, c: dict, seqs: np.ndarray, fp8: bool = False, chunk: int = 8):
    ci, key = _items(c), bw.seed_key(seed)
    n = len(seqs)
    full = np.concatenate([seqs, np.zeros(((-n) % chunk, seqs.shape[1]), seqs.dtype)])
    embed = _model_wide(key, ci)["embed"]
    xs = [embed[jnp.asarray(full[i:i + chunk])] for i in range(0, len(full), chunk)]
    step = jax.jit(lambda w, x: _block(w, x, dict(ci), fp8)[0])
    for i in range(c["num_hidden_layers"]):
        w = _layer(key, c, i)
        xs = [step(w, x) for x in xs]
    return jnp.concatenate(xs)[:n]


def head(seed: int, c: dict, xs, fp8: bool = False):
    p = _model_wide(bw.seed_key(seed), _items(c))
    return _logits(xs, p, c, fp8)[:, : c["vocab_size"]]


def score(seed: int, c: dict, xs, candidates: dict, fp8: bool = False) -> dict:
    lg = head(seed, c, xs, fp8)
    out = {"best": lg.max(-1), "top": lg.argmax(-1),
           **{n: jnp.take_along_axis(lg, jnp.asarray(t)[:, None], 1)[:, 0]
              for n, t in candidates.items()}}
    return {k: np.asarray(v) for k, v in out.items()}


def _params(seed: int, c: dict) -> dict:
    key = bw.seed_key(seed)
    out = dict(_model_wide(key, _items(c)))
    for i in range(c["num_hidden_layers"]):
        out.update({f"{n}.{i}": w for n, w in _layer(key, c, i).items()})
    return out


def _row_loss(p: dict, tokens, c: dict, fp8: bool):
    x, z = p["embed"][tokens], 0.0
    for i in range(c["num_hidden_layers"]):
        w = {n: p[f"{n}.{i}"] for n in _names(c, i)}
        x, zi = jax.checkpoint(lambda w, x: _block(w, x, c, fp8))(w, x)
        z = z + zi
    lg = _logits(x[:, :-1], p, c, fp8)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    moe_layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold) + LOAD_BALANCE * moe_layers + Z_LOSS * z


def train(seed: int, c: dict, batches: list, hp: dict, fp8: bool = False,
          rows_used: float = 1.0) -> dict:
    cc = dict(_items(c))
    return ref.adamw(lambda: _params(seed, c), lambda p, t: _row_loss(p, t, cc, fp8),
                     batches, hp, rows_used)


# -- counts ----------------------------------------------------------------------


def _active(c: dict) -> int:
    """Weights of the blocks that take part in a token's matrix products."""

    d, fm = c["hidden_size"], c["moe_intermediate_size"]
    attn = model_flops.layer_params(c) - 3 * d * c["intermediate_size"]
    first = c["first_k_dense_replace"]
    moe = d * c["n_routed_experts"] + 3 * d * fm * (
        c["num_experts_per_tok"] + c["n_shared_experts"])
    return (c["num_hidden_layers"] * attn + first * 3 * d * c["intermediate_size"]
            + (c["num_hidden_layers"] - first) * moe)


def decode_flops(c: dict, context: int) -> int:
    return 2 * _active(c) + model_flops.attention_flops(c, context) + model_flops.head_flops(c)


def prompt_flops(c: dict, prompt_len: int) -> int:
    n = prompt_len
    return (2 * _active(c) * n + model_flops.attention_flops(c, n * (n + 1) // 2)
            + model_flops.head_flops(c))


def decode_step_bytes(c: dict, contexts: list, itemsize: int = 2) -> int:
    """Every weight once (every expert is chosen), the keys and values each
    row attends to, and the new key and value it writes."""

    d, V, L = c["hidden_size"], c["padded_vocab_size"], c["num_hidden_layers"]
    weights_ = _active(c) + 2 * V * d + L * 2 * d + d
    kv = model_flops.kv_bytes_per_token(c, itemsize)
    return weights_ * itemsize + sum(kv * (n + 1) for n in contexts)


def train_flops_per_sequence(c: dict, seq: int) -> int:
    fwd = ((2 * _active(c) + model_flops.head_flops(c)) * seq
           + model_flops.attention_flops(c, seq * (seq + 1) // 2))
    return 3 * fwd
