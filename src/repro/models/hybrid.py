"""SSM and hybrid language models.

* ``ssm_lm``   — pure Mamba2 LM (mamba2-1.3b): embed → L × mamba block →
  norm → unembed. Attention-free; decode carries (state, conv) caches.
* ``hybrid_lm`` — Zamba2-style (zamba2-1.2b, arXiv:2411.15242): Mamba2
  backbone with ONE weight-shared attention+MLP block applied after every
  ``attn_every`` mamba blocks. Weights are shared across call sites, but
  each call site keeps its own KV cache.

Structure for scan: ``n_groups = L // attn_every`` groups of
(attn_every mamba blocks + shared-attn application) + ``L % attn_every``
trailing mamba blocks (zamba2: 38 = 6×6 + 2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T


def _init_mamba_block(cfg: ModelConfig, key) -> dict:
    k1, = jax.random.split(key, 1)
    return {"norm": L.init_norm(cfg, cfg.d_model),
            "mamba": S.init_mamba(cfg, k1)}


def _mamba_block(params: dict, cfg: ModelConfig, h: jnp.ndarray):
    return h + S.mamba_apply(params["mamba"], cfg,
                             L.norm(cfg, params["norm"], h))


def _mamba_block_decode(params: dict, cfg: ModelConfig, h, cache: S.SSMCache):
    y, new_cache = S.mamba_decode(params["mamba"], cfg,
                                  L.norm(cfg, params["norm"], h), cache)
    return h + y, new_cache


# --------------------------------------------------------------------------
# pure SSM LM
# --------------------------------------------------------------------------

def init_ssm_lm(cfg: ModelConfig, key) -> dict:
    k_emb, k_blocks = jax.random.split(key)
    return {
        "embed": L.init_embedding(cfg, k_emb),
        "blocks": T._stack_init(lambda k: _init_mamba_block(cfg, k),
                                k_blocks, cfg.num_layers),
        "final_norm": L.init_norm(cfg, cfg.d_model),
    }


def apply_ssm_lm_hidden(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
                        extra_embeds=None):
    del extra_embeds
    h = L.embed(params["embed"], cfg, tokens)

    def body(h, block_params):
        return _mamba_block(block_params, cfg, h), None

    h = T.scan_layers(body, h, params["blocks"], cfg.remat)
    return L.norm(cfg, params["final_norm"], h), T.zero_aux()


def apply_ssm_lm(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
                 extra_embeds=None):
    h, aux = apply_ssm_lm_hidden(cfg, params, tokens, extra_embeds)
    return L.unembed(params["embed"], cfg, h), aux


def init_ssm_cache(cfg: ModelConfig, params: dict, batch: int, max_len: int,
                   extra_embeds=None) -> dict:
    del params, max_len, extra_embeds
    single = S.mamba_init_cache(cfg, batch, cfg.cdtype)
    return {"ssm": jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (cfg.num_layers,) + x.shape),
        single)}


def decode_ssm_lm(cfg: ModelConfig, params: dict, cache: dict,
                  tokens: jnp.ndarray, pos) -> tuple[jnp.ndarray, dict]:
    del pos  # SSM decode is position-free (state carries history)
    h = L.embed(params["embed"], cfg, tokens)

    def body(h, xs):
        block_params, c = xs
        h, new_c = _mamba_block_decode(block_params, cfg, h, c)
        return h, new_c

    h, new_cache = jax.lax.scan(body, h, (params["blocks"], cache["ssm"]))
    h = L.norm(cfg, params["final_norm"], h)
    return L.unembed(params["embed"], cfg, h), {"ssm": new_cache}


# --------------------------------------------------------------------------
# Zamba2 hybrid LM
# --------------------------------------------------------------------------

def _hybrid_layout(cfg: ModelConfig) -> tuple[int, int]:
    n = max(cfg.attn_every, 1)
    return cfg.num_layers // n, cfg.num_layers % n   # (groups, trailing)


def init_hybrid_lm(cfg: ModelConfig, key) -> dict:
    groups, rem = _hybrid_layout(cfg)
    k_emb, k_g, k_r, k_a = jax.random.split(key, 4)
    p = {
        "embed": L.init_embedding(cfg, k_emb),
        "groups": T._stack_init(
            lambda k: jax.vmap(lambda kk: _init_mamba_block(cfg, kk))(
                jax.random.split(k, cfg.attn_every)), k_g, groups),
        "shared_attn": T.init_layer(cfg, k_a, kind="attn"),
        "final_norm": L.init_norm(cfg, cfg.d_model),
    }
    if rem:
        p["trailing"] = T._stack_init(
            lambda k: _init_mamba_block(cfg, k), k_r, rem)
    return p


def apply_hybrid_lm_hidden(cfg: ModelConfig, params: dict,
                           tokens: jnp.ndarray, extra_embeds=None):
    del extra_embeds
    b, s = tokens.shape
    h = L.embed(params["embed"], cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    mask = ("causal", None)
    shared = params["shared_attn"]

    def group_body(h, group_params):
        # nested remat: one mamba block's intermediates live at a time in
        # the group backward (measured 23.1 -> 8.6 GiB/dev on zamba2).
        def blk(bp, h2):
            return _mamba_block(bp, cfg, h2)

        def attn(h2):
            return T.layer_apply(shared, cfg, h2, positions, mask)[0]

        if cfg.remat:
            blk = jax.checkpoint(blk)
            attn = jax.checkpoint(attn)

        def inner(h2, bp):
            return blk(bp, h2), None
        h, _ = jax.lax.scan(inner, h, group_params)
        h = attn(h)                                   # weight-shared
        return h, None

    h = T.scan_layers(group_body, h, params["groups"], cfg.remat)
    if "trailing" in params:
        def inner(h2, bp):
            return _mamba_block(bp, cfg, h2), None
        h, _ = jax.lax.scan(inner, h, params["trailing"])
    return L.norm(cfg, params["final_norm"], h), T.zero_aux()


def apply_hybrid_lm(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
                    extra_embeds=None):
    h, aux = apply_hybrid_lm_hidden(cfg, params, tokens, extra_embeds)
    return L.unembed(params["embed"], cfg, h), aux


def init_hybrid_cache(cfg: ModelConfig, params: dict, batch: int,
                      max_len: int, extra_embeds=None) -> dict:
    del params, extra_embeds
    groups, rem = _hybrid_layout(cfg)
    single = S.mamba_init_cache(cfg, batch, cfg.cdtype)
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_
    cache = {
        "ssm": jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x[None, None], (groups, cfg.attn_every) + x.shape).copy(),
            single),
        "k": jnp.zeros((groups, batch, max_len, hkv * hd), cfg.cdtype),
        "v": jnp.zeros((groups, batch, max_len, hkv * hd), cfg.cdtype),
    }
    if rem:
        cache["ssm_trailing"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (rem,) + x.shape).copy(),
            single)
    return cache


def decode_hybrid_lm(cfg: ModelConfig, params: dict, cache: dict,
                     tokens: jnp.ndarray, pos) -> tuple[jnp.ndarray, dict]:
    h = L.embed(params["embed"], cfg, tokens)
    shared = params["shared_attn"]

    def group_body(h, xs):
        group_params, ssm_c, k_c, v_c = xs

        def inner(h2, inner_xs):
            bp, c = inner_xs
            h2, new_c = _mamba_block_decode(bp, cfg, h2, c)
            return h2, new_c

        h, new_ssm = jax.lax.scan(inner, h, (group_params, ssm_c))
        h, nk, nv = T.layer_decode(shared, cfg, h, k_c, v_c, pos)
        return h, (new_ssm, nk, nv)

    h, (new_ssm, nk, nv) = jax.lax.scan(
        group_body, h,
        (params["groups"], cache["ssm"], cache["k"], cache["v"]))
    new_cache = dict(cache, ssm=new_ssm, k=nk, v=nv)
    if "trailing" in params:
        def inner(h2, inner_xs):
            bp, c = inner_xs
            h2, new_c = _mamba_block_decode(bp, cfg, h2, c)
            return h2, new_c
        h, new_tr = jax.lax.scan(inner, h,
                                 (params["trailing"], cache["ssm_trailing"]))
        new_cache["ssm_trailing"] = new_tr
    h = L.norm(cfg, params["final_norm"], h)
    return L.unembed(params["embed"], cfg, h), new_cache
