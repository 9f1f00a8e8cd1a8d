"""Operations and bytes the algorithms need, from shapes alone.

The conventions, fixed here so that every PR computes them alike; the
sizes come from the configuration's architecture module
(``chipbench/arch/``):

* Training FLOPs per token are ``6 * N_matmul + 3 * A(S)`` (PaLM,
  appendix B), where ``A(S)`` is the forward attention of one token over
  the full sequence (``4 * L * S * d_attn`` for multi-head attention,
  so ``12 * L * S * d_attn`` in all): N_matmul counts the weights that
  enter a matrix product once per token (the tied head once, only the
  experts a token is routed to); biases, norm scales and the embedding
  gather are not matrix products; attention is counted over the full
  S x S square, not halved for the causal mask; nothing recomputed
  counts.
* The layer-wise update needs, per parameter, a read of the weight, the
  gradient and the momentum and a write of the momentum and the weight,
  at their storage widths (20 bytes per float32 parameter).
* Serving FLOPs count ``2 * N`` per token through each layer's matrices,
  ``A(context)`` per token for attention (over the live context,
  causal), and the head only where a logit is needed: the last prompt
  position and every decoded token.
* A decode step's attention needs each active slot's live K/V context
  read once, its query read and its output written, per layer.
* A whole decode step needs every stored weight read once at its
  storage width (the tied head once), each active slot's live K/V
  context read, and the K/V of its new token written. Activations,
  logits and the inactive slots of the pool are left out: the count is
  the least the step must move.
"""
from __future__ import annotations

from chipbench import arch


def train_flops_per_token(config: dict, seq: int) -> float:
    m = arch.load(config)
    return 6.0 * m.matmul_params_per_token(config) \
        + 3.0 * m.attention_flops_per_token(config, seq)


def update_bytes(config: dict, itemsize: int = 4) -> int:
    """Bytes one layer-wise (LARS/TVLARS) update needs: read w, g, m;
    write m, w."""
    return 5 * itemsize * arch.load(config).param_count(config)


def prefill_flops(config: dict, prompt_len: int) -> float:
    """One prompt: every position through the layers, causal attention,
    the head at the last position."""
    m, n = arch.load(config), prompt_len
    head = m.head_params(config)
    body = m.matmul_params_per_token(config) - head
    attn = m.attention_flops_per_token(config, 1) * n * (n + 1) / 2
    return 2.0 * body * n + attn + 2.0 * head


def decode_flops(config: dict, context: int) -> float:
    """One decoded token attending over ``context`` positions."""
    m = arch.load(config)
    return 2.0 * m.matmul_params_per_token(config) \
        + m.attention_flops_per_token(config, context)


def decode_attention_bytes(config: dict, contexts, itemsize: int = 2) -> int:
    """One decode step of the attention kernels over all layers: each
    active slot's live K/V, its query and its output."""
    m = arch.load(config)
    kv = sum(contexts) * m.kv_bytes_per_token(config, itemsize)
    return kv + len(contexts) * m.decode_io_bytes(config, itemsize)


def decode_step_bytes(config: dict, contexts) -> int:
    """One decode step of the whole model over the active slots, whose
    ``contexts`` count the positions each attends over (its new token
    included): the weights at the configuration's ``param_dtype``, the
    live K/V read and the new K/V written at its ``cache_dtype``."""
    import jax.numpy as jnp
    m = arch.load(config)
    w = jnp.dtype(config["program"]["param_dtype"]).itemsize
    kv = m.kv_bytes_per_token(
        config, jnp.dtype(config["serve"]["cache_dtype"]).itemsize)
    return m.param_count(config) * w + (sum(contexts) + len(contexts)) * kv
