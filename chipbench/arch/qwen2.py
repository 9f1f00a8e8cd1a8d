"""Qwen2 (``model_type`` ``qwen2``): the dense decoder of the Qwen2 and
Qwen2.5 configurations, in the program's parametrisation.

The model, written out in ``jax.numpy`` at float32: token embedding
scaled by sqrt(hidden_size), pre-norm layers ``h += attn(norm(h));
h += mlp(norm(h))`` with RMSNorm ``x / rms(x) * (1 + scale)``, rotary
embedding on the two halves of each head, grouped-query causal softmax
attention with q/k/v biases, a SiLU-gated MLP, a final RMSNorm and the
tied head. The embedding scale and the ``1 + scale`` norm weights are
the program's stated parametrisation (the published Qwen2 has neither);
see PERF.md.

Weights live in the layout the program stores (layers stacked on a
leading axis). Every stored tensor of two or more dimensions is a
trust-ratio group, as in the program; the final norm's scale takes the
plain rate.

The counts follow ``chipbench/flops.py``'s conventions: the matrices of
q, k, v, o, the three MLP matrices and the tied head enter a matrix
product; biases, norm scales and the embedding gather do not.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from chipbench import reference

F32 = jnp.float32


class Shape(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float


def shape_of(config: dict) -> Shape:
    d, h = config["hidden_size"], config["num_attention_heads"]
    return Shape(config["num_hidden_layers"], d, h,
                 config["num_key_value_heads"],
                 config.get("head_dim", d // h), config["intermediate_size"],
                 config["vocab_size"], float(config["rope_theta"]),
                 float(config["rms_norm_eps"]))


def program_config(config: dict):
    from repro.configs.base import ModelConfig
    p = config["program"]
    return ModelConfig(
        arch_id=config["name"], family="dense", source=config["source"],
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        qkv_bias=p["qkv_bias"], tie_embeddings=config["tie_word_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        param_dtype=p["param_dtype"], compute_dtype=p["compute_dtype"],
        remat=p["remat"])


# --------------------------------------------------------------------------
# weights: the benchmark's own, from the seed, in the program's layout
# --------------------------------------------------------------------------

def _table(s: Shape) -> list[tuple[tuple[str, ...], tuple, str]]:
    """(path, shape, init kind) of every weight, in a fixed order."""
    L, d, h, kv, hd, f = s.layers, s.d, s.heads, s.kv_heads, s.head_dim, \
        s.ffn
    g = ("groups", "l0_attn")
    return [
        (("embed", "table"), (s.vocab, d), "embed"),
        (g + ("attn", "bk"), (L, kv, hd), "bias"),
        (g + ("attn", "bq"), (L, h, hd), "bias"),
        (g + ("attn", "bv"), (L, kv, hd), "bias"),
        (g + ("attn", "wk"), (L, d, kv, hd), "matrix"),
        (g + ("attn", "wo"), (L, h, hd, d), "matrix"),
        (g + ("attn", "wq"), (L, d, h, hd), "matrix"),
        (g + ("attn", "wv"), (L, d, kv, hd), "matrix"),
        (g + ("mlp", "wg"), (L, d, f), "matrix"),
        (g + ("mlp", "wi"), (L, d, f), "matrix"),
        (g + ("mlp", "wo"), (L, f, d), "matrix"),
        (g + ("norm1", "scale"), (L, d), "norm"),
        (g + ("norm2", "scale"), (L, d), "norm"),
        (("final_norm", "scale"), (d,), "final_norm"),
    ]


def leaves(config: dict) -> list[tuple[tuple[str, ...], tuple, str]]:
    return [(path, shape, "adapt" if len(shape) >= 2 else "plain")
            for path, shape, _ in _table(shape_of(config))]


def init_weights(config: dict, dtype):
    """``key -> weights``: normal draws at the scales the configuration's
    ``init`` names, one folded key per leaf."""
    init = config["init"]
    table = _table(shape_of(config))

    def make(key) -> dict:
        pairs = []
        for i, (path, shape, kind) in enumerate(table):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            if kind == "final_norm":
                x = init["final_norm_mean"] + init["norm_std"] * z
            else:
                x = init[f"{kind}_std"] * z
            pairs.append((path, x.astype(dtype)))
        return reference.nest(pairs)

    return make


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

def rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def rope(x, theta):
    """x: [S, H, Dh] at positions 0..S-1; rotates the two halves."""
    s, _, dh = x.shape
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(a: Shape, mm, x, p):
    s = x.shape[0]
    up = jax.tree_util.tree_map(lambda t: t.astype(F32), p)
    at, ml = up["attn"], up["mlp"]
    y = rms(x, up["norm1"]["scale"], a.eps)
    q = mm("sd,dhk->shk", y, at["wq"]) + at["bq"]
    k = mm("sd,dhk->shk", y, at["wk"]) + at["bk"]
    v = mm("sd,dhk->shk", y, at["wv"]) + at["bv"]
    q, k = rope(q, a.rope_theta), rope(k, a.rope_theta)
    rep = jnp.arange(a.heads) // (a.heads // a.kv_heads)
    k, v = k[:, rep], v[:, rep]
    scores = mm("qhd,khd->hqk", q, k) / math.sqrt(a.head_dim)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mm("hqk,khd->qhd", probs, v)
    x = x + mm("qhd,hdm->qm", o, at["wo"])
    y = rms(x, up["norm2"]["scale"], a.eps)
    gate = jax.nn.silu(mm("sd,df->sf", y, ml["wg"]))
    x = x + mm("sf,fd->sd", gate * mm("sd,df->sf", y, ml["wi"]), ml["wo"])
    return x


def hidden(a: Shape, mm, params: dict, tokens, remat: bool = False):
    """tokens [S] -> final-normed hidden states [S, d] (float32).
    ``remat`` recomputes each layer in the backward pass (same values,
    less memory)."""
    table = params["embed"]["table"]
    x = jnp.take(table, tokens, axis=0).astype(F32) * math.sqrt(a.d)

    def body(x, p):
        return _layer(a, mm, x, p), None

    x, _ = jax.lax.scan(jax.checkpoint(body) if remat else body, x,
                        params["groups"]["l0_attn"])
    return rms(x, params["final_norm"]["scale"], a.eps)


def logits(config: dict, mm, params: dict, ids):
    """ids [S] -> logits [S, V] (float32)."""
    return mm("sd,vd->sv", hidden(shape_of(config), mm, params, ids),
              params["embed"]["table"])


CE_CHUNK = 512


def row_loss(config: dict, mm, params: dict, row):
    """Mean next-token cross-entropy of one row of ``seq + 1`` ids,
    taken over blocks of positions so that one block of logits lives
    at a time; no aux."""
    x = hidden(shape_of(config), mm, params, row[:-1], remat=True)
    s = x.shape[0]
    c = CE_CHUNK if s % CE_CHUNK == 0 else s
    table = params["embed"]["table"]

    @jax.checkpoint
    def block(xb, yb):
        lg = mm("sd,vd->sv", xb, table)
        gold = jnp.take_along_axis(lg, yb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    def body(total, xs):
        return total + block(*xs), None

    total, _ = jax.lax.scan(body, jnp.zeros((), F32),
                            (x.reshape(s // c, c, -1),
                             row[1:].reshape(s // c, c)))
    return total / s, {}


def after_step(config: dict, params: dict, aux: dict) -> dict:
    """Qwen2 holds no leaf outside the optimizer."""
    return params


# --------------------------------------------------------------------------
# counts
# --------------------------------------------------------------------------

def layer_matmul_params(config: dict) -> int:
    s = shape_of(config)
    q_o = 2 * s.d * s.heads * s.head_dim
    k_v = 2 * s.d * s.kv_heads * s.head_dim
    return q_o + k_v + 3 * s.d * s.ffn


def head_params(config: dict) -> int:
    return config["vocab_size"] * config["hidden_size"]


def matmul_params_per_token(config: dict) -> int:
    """N_matmul with the tied head counted once."""
    return config["num_hidden_layers"] * layer_matmul_params(config) \
        + head_params(config)


def param_count(config: dict) -> int:
    """Every stored parameter: matrices, q/k/v biases, norm scales."""
    s = shape_of(config)
    biases = (s.heads + 2 * s.kv_heads) * s.head_dim
    norms = 2 * s.d
    return matmul_params_per_token(config) + s.layers * (biases + norms) \
        + s.d


def attention_flops_per_token(config: dict, context: int) -> float:
    """q.k and p.v of every head over ``context`` positions."""
    s = shape_of(config)
    return 4.0 * s.layers * s.heads * s.head_dim * context


def kv_bytes_per_token(config: dict, itemsize: int) -> int:
    s = shape_of(config)
    return s.layers * 2 * s.kv_heads * s.head_dim * itemsize


def decode_io_bytes(config: dict, itemsize: int) -> int:
    s = shape_of(config)
    return s.layers * 2 * s.heads * s.head_dim * itemsize
