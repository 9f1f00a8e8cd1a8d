"""Whisper-style encoder–decoder (whisper-large-v3, arXiv:2212.04356).

The mel-spectrogram + conv frontend is a STUB per the assignment:
``batch["frames"]`` carries precomputed frame embeddings
[B, encoder_seq, d_model]. The transformer backbone is real:

  encoder: L_enc × (bidirectional self-attn + MLP), LayerNorm, GELU
  decoder: L_dec × (causal self-attn + cross-attn to encoder + MLP)

Adaptations (DESIGN.md §8): RoPE instead of Whisper's learned/sinusoidal
positions (avoids a 32k learned table for the assigned decode shapes);
LayerNorm + GELU retained via cfg.norm/cfg.act.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T


def _init_dec_layer(cfg: ModelConfig, key) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm1": L.init_norm(cfg, cfg.d_model),
        "self_attn": L.init_attention(cfg, k1),
        "norm_x": L.init_norm(cfg, cfg.d_model),
        "cross_attn": L.init_attention(cfg, k2),
        "norm2": L.init_norm(cfg, cfg.d_model),
        "mlp": L.init_mlp(cfg, k3),
    }


def init_encdec(cfg: ModelConfig, key) -> dict:
    k_emb, k_enc, k_dec, _ = jax.random.split(key, 4)
    return {
        "embed": L.init_embedding(cfg, k_emb),
        "encoder": T._stack_init(
            lambda k: T.init_layer(cfg, k, kind="attn"), k_enc,
            cfg.encoder_layers),
        "enc_norm": L.init_norm(cfg, cfg.d_model),
        "decoder": T._stack_init(lambda k: _init_dec_layer(cfg, k), k_dec,
                                 cfg.num_layers),
        "final_norm": L.init_norm(cfg, cfg.d_model),
    }


def encode(cfg: ModelConfig, params: dict, frames: jnp.ndarray
           ) -> jnp.ndarray:
    """frames: [B, S_enc, d] (stub frontend output) -> encoder states."""
    b, s, _ = frames.shape
    h = frames.astype(cfg.cdtype)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def body(h, lp):
        h, _ = T.layer_apply(lp, cfg, h, positions, None)  # bidirectional
        return h, None

    h = T.scan_layers(body, h, params["encoder"], cfg.remat)
    return L.norm(cfg, params["enc_norm"], h)


def _dec_layer_apply(lp: dict, cfg: ModelConfig, h, positions, mask, enc):
    h = h + L.attention(lp["self_attn"], cfg,
                        L.norm(cfg, lp["norm1"], h), positions, mask)
    h = h + L.attention(lp["cross_attn"], cfg,
                        L.norm(cfg, lp["norm_x"], h), positions, None,
                        kv_src=enc, use_rope=False)
    return h + L.mlp(lp["mlp"], cfg, L.norm(cfg, lp["norm2"], h))


def apply_encdec_hidden(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
                        extra_embeds: Optional[jnp.ndarray] = None):
    """tokens: [B,S_dec]; extra_embeds: [B, S_enc, d] frame embeddings."""
    assert extra_embeds is not None, "encdec needs frame embeddings"
    enc = encode(cfg, params, extra_embeds)
    b, s = tokens.shape
    h = L.embed(params["embed"], cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    mask = ("causal", None)

    def body(h, lp):
        return _dec_layer_apply(lp, cfg, h, positions, mask, enc), None

    h = T.scan_layers(body, h, params["decoder"], cfg.remat)
    return L.norm(cfg, params["final_norm"], h), T.zero_aux()


def apply_encdec(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
                 extra_embeds: Optional[jnp.ndarray] = None):
    h, aux = apply_encdec_hidden(cfg, params, tokens, extra_embeds)
    return L.unembed(params["embed"], cfg, h), aux


def init_encdec_cache(cfg: ModelConfig, params: dict, batch: int,
                      max_len: int,
                      extra_embeds: Optional[jnp.ndarray] = None) -> dict:
    """Runs the encoder once and precomputes per-layer cross K/V."""
    assert extra_embeds is not None
    enc = encode(cfg, params, extra_embeds)
    ck, cv = jax.vmap(
        lambda lp: T.cross_kv_from_embeds({"attn": lp["cross_attn"]},
                                          cfg, enc))(params["decoder"])
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_
    ldec = cfg.num_layers
    return {
        "k": jnp.zeros((ldec, batch, max_len, hkv * hd), cfg.cdtype),
        "v": jnp.zeros((ldec, batch, max_len, hkv * hd), cfg.cdtype),
        "ck": ck, "cv": cv,
    }


def decode_encdec(cfg: ModelConfig, params: dict, cache: dict,
                  tokens: jnp.ndarray, pos) -> tuple[jnp.ndarray, dict]:
    h = L.embed(params["embed"], cfg, tokens)

    def body(h, xs):
        lp, k_c, v_c, ck, cv = xs
        x = L.norm(cfg, lp["norm1"], h)
        a, nk, nv = L.attention_decode(lp["self_attn"], cfg, x, k_c, v_c,
                                       pos)
        h = h + a
        x = L.norm(cfg, lp["norm_x"], h)
        q = jnp.einsum("bsd,dhk->bshk", x,
                       lp["cross_attn"]["wq"].astype(x.dtype))
        if cfg.qkv_bias:
            q = q + lp["cross_attn"]["bq"].astype(x.dtype)
        out = L.gqa_scores_apply(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                 None)
        h = h + jnp.einsum("bshk,hkd->bsd", out,
                           lp["cross_attn"]["wo"].astype(x.dtype))
        h = h + L.mlp(lp["mlp"], cfg, L.norm(cfg, lp["norm2"], h))
        return h, (nk, nv)

    h, (nk, nv) = jax.lax.scan(
        body, h, (params["decoder"], cache["k"], cache["v"],
                  cache["ck"], cache["cv"]))
    h = L.norm(cfg, params["final_norm"], h)
    return (L.unembed(params["embed"], cfg, h),
            dict(cache, k=nk, v=nv))
