"""Operations and bytes the algorithms need, from shapes alone.

The conventions, fixed here so that every PR computes them alike:

* Training FLOPs per token are ``6 * N_matmul + 12 * L * S * d``
  (PaLM, appendix B): N_matmul counts the weights that enter a matrix
  product once each (q, k, v, o, the three MLP matrices, and the tied
  head once); biases, norm scales and the embedding gather are not
  matrix products; attention is counted over the full S x S square, not
  halved for the causal mask; nothing recomputed counts.
* The layer-wise update needs, per parameter, a read of the weight, the
  gradient and the momentum and a write of the momentum and the weight,
  at their storage widths (20 bytes per float32 parameter).
* Serving FLOPs count ``2 * N`` per token through each layer's matrices,
  ``4 * d_attn * context`` per token per layer for attention (q.k and
  p.v over the live context, causal), and the head only where a logit
  is needed: the last prompt position and every decoded token.
* A decode step's attention needs each active slot's live K/V context
  read once, its query read and its output written, per layer.
"""
from __future__ import annotations

from typing import NamedTuple


class Shape(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int


def shape_of(config: dict) -> Shape:
    d, h = config["hidden_size"], config["num_attention_heads"]
    return Shape(config["num_hidden_layers"], d, h,
                 config["num_key_value_heads"],
                 config.get("head_dim", d // h),
                 config["intermediate_size"], config["vocab_size"])


def layer_matmul_params(s: Shape) -> int:
    q_o = 2 * s.d * s.heads * s.head_dim
    k_v = 2 * s.d * s.kv_heads * s.head_dim
    return q_o + k_v + 3 * s.d * s.ffn


def head_params(s: Shape) -> int:
    return s.vocab * s.d


def matmul_params(s: Shape) -> int:
    """N_matmul with the tied head counted once."""
    return s.layers * layer_matmul_params(s) + head_params(s)


def param_count(s: Shape) -> int:
    """Every stored parameter: matrices, q/k/v biases, norm scales."""
    biases = (s.heads + 2 * s.kv_heads) * s.head_dim
    norms = 2 * s.d
    return matmul_params(s) + s.layers * (biases + norms) + s.d


def train_flops_per_token(s: Shape, seq: int) -> float:
    return 6.0 * matmul_params(s) + 12.0 * s.layers * seq * s.d


def update_bytes(s: Shape, itemsize: int = 4) -> int:
    """Bytes one layer-wise (LARS/TVLARS) update needs: read w, g, m;
    write m, w."""
    return 5 * itemsize * param_count(s)


def kv_bytes_per_token(s: Shape, itemsize: int = 2) -> int:
    """K and V of one token over all layers."""
    return s.layers * 2 * s.kv_heads * s.head_dim * itemsize


def prefill_flops(s: Shape, prompt_len: int) -> float:
    """One prompt: every position through the layers, causal attention,
    the head at the last position."""
    n = prompt_len
    attn = 4.0 * s.layers * s.heads * s.head_dim * n * (n + 1) / 2
    return 2.0 * s.layers * layer_matmul_params(s) * n + attn \
        + 2.0 * head_params(s)


def decode_flops(s: Shape, context: int) -> float:
    """One decoded token attending over ``context`` positions."""
    return 2.0 * matmul_params(s) \
        + 4.0 * s.layers * s.heads * s.head_dim * context


def decode_attention_bytes(s: Shape, contexts, itemsize: int = 2) -> int:
    """One decode step of the attention kernels over all layers: each
    active slot's live K/V, its query and its output."""
    kv = sum(contexts) * kv_bytes_per_token(s, itemsize)
    qo = len(contexts) * s.layers * 2 * s.heads * s.head_dim * itemsize
    return kv + qo
