"""Decoder-only transformer LM — dense / MoE / gemma3-local:global / VLM.

Layer stacks are built as *groups* scanned with ``jax.lax.scan`` over
stacked parameters (HLO size independent of depth — a 80-layer 72B model
traces one group). Group patterns:

  dense / moe        group = 1 uniform layer,            G = num_layers
  gemma3 (global_every=N, sliding_window=W)
                     group = (N−1) local + 1 global,     G = L / N
  vlm (cross_attn_every=N)
                     group = N self + 1 gated cross,     G = L / N
                     (cross blocks are the *extra* adapter layers of
                      Llama-3.2-Vision; "40L" = 40 self-attn layers)

Each layer is pre-norm: h += attn(norm(h)); h += mlp|moe(norm(h)).
MoE aux losses are accumulated through the scan carry.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.obs import scopes


class LMAux(NamedTuple):
    load_balance_loss: jnp.ndarray
    router_z_loss: jnp.ndarray


def zero_aux() -> LMAux:
    """Fresh all-zero aux losses.

    A function, not a module constant: a module-level ``jnp.zeros``
    initializes the jax backend at IMPORT time, which silently pins the
    device count before launchers can set
    ``XLA_FLAGS=--xla_force_host_platform_device_count`` (the mesh.py
    import contract)."""
    return LMAux(jnp.zeros(()), jnp.zeros(()))


# --------------------------------------------------------------------------
# single layers
# --------------------------------------------------------------------------

def init_layer(cfg: ModelConfig, key, kind: str = "attn") -> dict:
    """kind: attn | local | cross — all attn+ffn blocks."""
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"norm1": L.init_norm(cfg, cfg.d_model),
         "attn": L.init_attention(cfg, k1),
         "norm2": L.init_norm(cfg, cfg.d_model)}
    if cfg.num_experts and kind != "cross":
        p["moe"] = M.init_moe(cfg, k2)
    else:
        p["mlp"] = L.init_mlp(cfg, k2)
    if kind == "cross":
        p["gate"] = jnp.zeros((), jnp.float32)   # tanh-gated (starts closed)
    del k3
    return p


def layer_apply(params: dict, cfg: ModelConfig, h: jnp.ndarray,
                positions: jnp.ndarray, mask, kind: str = "attn",
                kv_src: Optional[jnp.ndarray] = None
                ) -> tuple[jnp.ndarray, LMAux]:
    a = L.attention(params["attn"], cfg, L.norm(cfg, params["norm1"], h),
                    positions, mask, kv_src=kv_src,
                    use_rope=(kind != "cross"))
    if kind == "cross":
        a = jnp.tanh(params["gate"]).astype(a.dtype) * a
    h = h + a
    x = L.norm(cfg, params["norm2"], h)
    if "moe" in params:
        y, aux = M.moe_apply(params["moe"], cfg, x)
        return h + y, LMAux(aux.load_balance_loss, aux.router_z_loss)
    return h + L.mlp(params["mlp"], cfg, x), zero_aux()


def layer_decode(params: dict, cfg: ModelConfig, h: jnp.ndarray,
                 k_cache, v_cache, pos, *, window=None,
                 cross_kv=None, kind: str = "attn"):
    """One-token layer step; for kind=='cross' attends to cross_kv=(k,v)."""
    x = L.norm(cfg, params["norm1"], h)
    if kind == "cross":
        ck, cv = cross_kv
        q = jnp.einsum("bsd,dhk->bshk", x, params["attn"]["wq"].astype(
            x.dtype))
        if cfg.qkv_bias:
            q = q + params["attn"]["bq"].astype(x.dtype)
        out = L.gqa_scores_apply(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                 None)
        a = jnp.einsum("bshk,hkd->bsd", out,
                       params["attn"]["wo"].astype(x.dtype))
        a = jnp.tanh(params["gate"]).astype(a.dtype) * a
        new_k, new_v = k_cache, v_cache
    else:
        a, new_k, new_v = L.attention_decode(
            params["attn"], cfg, x, k_cache, v_cache, pos, window=window)
    h = h + a
    x = L.norm(cfg, params["norm2"], h)
    if "moe" in params:
        y, _ = M.moe_apply(params["moe"], cfg, x)
        h = h + y
    else:
        h = h + L.mlp(params["mlp"], cfg, x)
    return h, new_k, new_v


def layer_apply_kv(params: dict, cfg: ModelConfig, h: jnp.ndarray,
                   positions: jnp.ndarray, mask, kind: str = "attn",
                   kv_src: Optional[jnp.ndarray] = None):
    """``layer_apply`` that also returns the layer's (rope'd) K/V —
    the prefill forward's cache dump. MoE aux losses are dropped
    (inference path). Returns (h, (k, v))."""
    a, kv = L.attention(params["attn"], cfg,
                        L.norm(cfg, params["norm1"], h),
                        positions, mask, kv_src=kv_src,
                        use_rope=(kind != "cross"), return_kv=True)
    if kind == "cross":
        a = jnp.tanh(params["gate"]).astype(a.dtype) * a
    h = h + a
    x = L.norm(cfg, params["norm2"], h)
    if "moe" in params:
        y, _ = M.moe_apply(params["moe"], cfg, x)
        return h + y, kv
    return h + L.mlp(params["mlp"], cfg, x), kv


def cross_kv_from_embeds(params: dict, cfg: ModelConfig,
                         embeds: jnp.ndarray):
    """Precompute cross-attention K/V from (image/encoder) embeddings."""
    k = jnp.einsum("btd,dhk->bthk", embeds,
                   params["attn"]["wk"].astype(embeds.dtype))
    v = jnp.einsum("btd,dhk->bthk", embeds,
                   params["attn"]["wv"].astype(embeds.dtype))
    if cfg.qkv_bias:
        k = k + params["attn"]["bk"].astype(embeds.dtype)
        v = v + params["attn"]["bv"].astype(embeds.dtype)
    return k, v


# --------------------------------------------------------------------------
# group structure
# --------------------------------------------------------------------------

def _group_spec(cfg: ModelConfig) -> tuple[int, list[str]]:
    """Returns (num_groups, [kind per layer-in-group])."""
    if cfg.family == "vlm" and cfg.cross_attn_every:
        n = cfg.cross_attn_every
        assert cfg.num_layers % n == 0
        return cfg.num_layers // n, ["attn"] * n + ["cross"]
    if cfg.global_every and cfg.sliding_window:
        n = cfg.global_every
        assert cfg.num_layers % n == 0
        return cfg.num_layers // n, ["local"] * (n - 1) + ["attn"]
    return cfg.num_layers, ["attn"]


def _stack_init(fn, key, count: int):
    return jax.vmap(fn)(jax.random.split(key, count))


def _sqrt_factor(n: int) -> int:
    """Largest divisor of n that is <= sqrt(n)."""
    best = 1
    d = 1
    while d * d <= n:
        if n % d == 0:
            best = d
        d += 1
    return best


def scan_layers(body, carry, stacked, remat: bool):
    """scan with sqrt(L) checkpointing.

    A flat remat scan saves the carry at EVERY step: [L, B, S, D] — and
    on the CPU/XLA backend the backward loop's convert(h)->f32 gets
    hoisted into a second full f32 stack (qwen2-72b train_4k: 5 + 10
    GiB/dev for 80 layers). Factoring L = outer × inner and
    checkpointing both levels saves only ``outer`` carries and
    recomputes inner segments on the fly — the standard sqrt-remat
    trade (one extra forward per inner segment).
    """
    leaves = jax.tree_util.tree_leaves(stacked)
    n = leaves[0].shape[0]
    inner = _sqrt_factor(n) if remat else 1
    if not remat or inner <= 1:
        b = jax.checkpoint(body) if remat else body
        carry, _ = jax.lax.scan(b, carry, stacked)
        return carry
    outer = n // inner
    stacked2 = jax.tree_util.tree_map(
        lambda x: x.reshape((outer, inner) + x.shape[1:]), stacked)
    inner_body = jax.checkpoint(body)

    def outer_body(c, xs):
        c, _ = jax.lax.scan(inner_body, c, xs)
        return c, None

    carry, _ = jax.lax.scan(jax.checkpoint(outer_body), carry, stacked2)
    return carry


def init_lm(cfg: ModelConfig, key) -> dict:
    groups, kinds = _group_spec(cfg)
    k_emb, k_layers, k_norm = jax.random.split(key, 3)
    layer_params = {}
    lkeys = jax.random.split(k_layers, len(kinds))
    for i, kind in enumerate(kinds):
        layer_params[f"l{i}_{kind}"] = _stack_init(
            lambda k, kind=kind: init_layer(cfg, k, kind), lkeys[i], groups)
    return {
        "embed": L.init_embedding(cfg, k_emb),
        "groups": layer_params,
        "final_norm": L.init_norm(cfg, cfg.d_model),
    }


def _group_apply(cfg: ModelConfig, kinds, group_params, h, positions,
                 masks, kv_src, aux: LMAux):
    # nested remat: each layer is checkpointed individually so the
    # backward of a multi-layer group (gemma3: 6 layers, vlm: 6) holds
    # ONE layer's intermediates, not the whole group's (measured
    # 40.9 -> 14.9 GiB/dev on gemma3 train_4k).
    nested = cfg.remat and len(kinds) > 1
    for i, kind in enumerate(kinds):
        p = group_params[f"l{i}_{kind}"]
        mask = masks["local"] if kind == "local" else masks["global"]
        src = kv_src if kind == "cross" else None

        def call(p_, h_, kind=kind, mask=mask, src=src):
            return layer_apply(p_, cfg, h_, positions,
                               None if kind == "cross" else mask, kind, src)

        h, a = (jax.checkpoint(call) if nested else call)(p, h)
        aux = LMAux(aux.load_balance_loss + a.load_balance_loss,
                    aux.router_z_loss + a.router_z_loss)
    return h, aux


def apply_lm_hidden(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
                    extra_embeds: Optional[jnp.ndarray] = None
                    ) -> tuple[jnp.ndarray, LMAux]:
    """Backbone forward up to the final norm (no unembed)."""
    groups, kinds = _group_spec(cfg)
    b, s = tokens.shape
    with jax.named_scope(scopes.EMBED):
        h = L.embed(params["embed"], cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    masks = {"global": ("causal", None),
             "local": ("causal", cfg.sliding_window)
             if cfg.sliding_window else None}
    kv_src = extra_embeds.astype(h.dtype) if extra_embeds is not None else None

    def body(carry, group_params):
        h, aux = carry
        h, aux = _group_apply(cfg, kinds, group_params, h, positions,
                              masks, kv_src, aux)
        return (h, aux), None

    with jax.named_scope(scopes.LAYERS):
        h, aux = scan_layers(body, (h, zero_aux()), params["groups"],
                             cfg.remat)
    with jax.named_scope(scopes.HEAD_LOSS):
        return L.norm(cfg, params["final_norm"], h), aux


def apply_lm(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
             extra_embeds: Optional[jnp.ndarray] = None
             ) -> tuple[jnp.ndarray, LMAux]:
    """Full-sequence forward. tokens: [B,S] -> logits [B,S,V]."""
    h, aux = apply_lm_hidden(cfg, params, tokens, extra_embeds)
    return L.unembed(params["embed"], cfg, h), aux


# --------------------------------------------------------------------------
# decode (KV cache)
# --------------------------------------------------------------------------

def init_lm_cache(cfg: ModelConfig, params: dict, batch: int, max_len: int,
                  extra_embeds: Optional[jnp.ndarray] = None) -> dict:
    groups, kinds = _group_spec(cfg)
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_
    dt = cfg.kv_dtype            # pool storage (bf16 pools stay bf16;
    cache: dict[str, Any] = {}   # decode upcasts to f32 on read)
    for i, kind in enumerate(kinds):
        name = f"l{i}_{kind}"
        if kind == "cross":
            assert extra_embeds is not None, "vlm cache needs image embeds"
            k, v = jax.vmap(
                lambda p: cross_kv_from_embeds(p, cfg,
                                               extra_embeds.astype(dt))
            )(params["groups"][name])
            cache[name] = {"ck": k, "cv": v}
        else:
            t = (min(cfg.sliding_window, max_len)
                 if kind == "local" and cfg.sliding_window else max_len)
            cache[name] = {             # heads merged: [G,B,T,Hkv·Dh]
                "k": jnp.zeros((groups, batch, t, hkv * hd), dt),
                "v": jnp.zeros((groups, batch, t, hkv * hd), dt)}
    return cache


def _prefill_cache_layout(cfg: ModelConfig, kind: str, k: jnp.ndarray,
                          v: jnp.ndarray, max_len: int,
                          lens: Optional[jnp.ndarray] = None) -> dict:
    """[G,B,S,Hkv,Dh] prefill K/V -> the ``init_lm_cache`` layout
    ``[G,B,T,Hkv·Dh]`` at ``max_len``: global layers zero-pad the
    sequence axis to T=max_len; local (sliding-window) layers gather
    each ROW's last ``min(lens[b], window)`` tokens into their ring
    slots (p % T_local) — byte-identical to what streaming that row's
    prompt through ``attention_decode`` leaves behind. ``lens`` [B] gives per-row
    prompt lengths for right-padded batches (None = every row is the
    full S); global layers need no masking because decode writes each
    new key at the row's depth BEFORE attending, so pad-position keys
    are overwritten or masked, never read."""
    g, b, s, hkv, hd = k.shape
    # prefill dump lands at pool storage dtype (same rounding as
    # decode's cache-row writes), heads merged
    k = k.astype(cfg.kv_dtype).reshape(g, b, s, hkv * hd)
    v = v.astype(cfg.kv_dtype).reshape(g, b, s, hkv * hd)
    if kind == "local" and cfg.sliding_window:
        t = min(cfg.sliding_window, max_len)
        last = (jnp.full((b,), s, jnp.int32) if lens is None
                else lens.astype(jnp.int32))[:, None] - 1   # [B,1]
        # ring slot q holds the LARGEST position p <= last with
        # p % t == q (exactly what decode's abs_pos arithmetic assumes)
        q = jnp.arange(t, dtype=jnp.int32)[None, :]         # [1,T]
        p = last - ((last - q) % t)                         # [B,T]
        valid = (p >= 0)[None, :, :, None]
        idx = jnp.broadcast_to(jnp.clip(p, 0, s - 1)[None, :, :, None],
                               (g, b, t, 1))
        kc = jnp.where(valid, jnp.take_along_axis(k, idx, axis=2), 0)
        vc = jnp.where(valid, jnp.take_along_axis(v, idx, axis=2), 0)
        return {"k": kc, "v": vc}
    pad = ((0, 0), (0, 0), (0, max_len - s), (0, 0))
    return {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}


def apply_lm_prefill(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
                     max_len: int,
                     extra_embeds: Optional[jnp.ndarray] = None,
                     lens: Optional[jnp.ndarray] = None
                     ) -> tuple[jnp.ndarray, dict]:
    """Single-shot batched prefill: ONE full-sequence forward that also
    dumps a decode-ready KV cache (the production path ``prefill_32k``
    lowers) — replacing the O(seq_len) token-by-token reference loop.
    tokens: [B,S]. Returns (logits [B,S,V], cache) where ``cache``
    matches ``init_lm_cache(..., max_len)`` after streaming the prompt
    through ``decode_lm`` (the parity-tested oracle). Right-padded
    prompts are safe: pad positions sit causally after every real
    token, and decode masks key positions beyond each row's depth —
    pass ``lens`` [B] so sliding-window layers ring-pack each row's
    own last ``window`` tokens instead of the padded suffix."""
    groups, kinds = _group_spec(cfg)
    b, s = tokens.shape
    if s > max_len:
        raise ValueError(f"prompt length {s} exceeds cache max_len "
                         f"{max_len}")
    h = L.embed(params["embed"], cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    masks = {"global": ("causal", None),
             "local": ("causal", cfg.sliding_window)
             if cfg.sliding_window else None}
    kv_src = extra_embeds.astype(h.dtype) if extra_embeds is not None \
        else None

    def body(h, group_params):
        kvs = {}
        for i, kind in enumerate(kinds):
            name = f"l{i}_{kind}"
            mask = masks["local"] if kind == "local" else masks["global"]
            h, kvs[name] = layer_apply_kv(
                group_params[name], cfg, h, positions,
                None if kind == "cross" else mask, kind,
                kv_src if kind == "cross" else None)
        return h, kvs

    # plain scan (no remat — inference): ys stack each layer's per-group
    # K/V to [G, B, S, Hkv, Dh]
    h, kvs = jax.lax.scan(body, h, params["groups"])
    cache: dict[str, Any] = {}
    for i, kind in enumerate(kinds):
        name = f"l{i}_{kind}"
        k, v = kvs[name]
        if kind == "cross":
            cache[name] = {"ck": k, "cv": v}
        else:
            cache[name] = _prefill_cache_layout(cfg, kind, k, v,
                                                max_len, lens)
    h = L.norm(cfg, params["final_norm"], h)
    return L.unembed(params["embed"], cfg, h), cache


def decode_lm(cfg: ModelConfig, params: dict, cache: dict,
              tokens: jnp.ndarray, pos: jnp.ndarray
              ) -> tuple[jnp.ndarray, dict]:
    """One-token step. tokens: [B,1]; pos: scalar int32 (tokens cached
    so far) or a [B] vector of per-row depths (the serving engine's
    continuous-batching path — see ``layers.attention_decode``).
    Returns (logits [B,1,V], new cache)."""
    groups, kinds = _group_spec(cfg)
    h = L.embed(params["embed"], cfg, tokens)

    def body(h, xs):
        group_params, group_cache = xs
        new_cache = {}
        for i, kind in enumerate(kinds):
            name = f"l{i}_{kind}"
            p = group_params[name]
            c = group_cache[name]
            if kind == "cross":
                h, _, _ = layer_decode(p, cfg, h, None, None, pos,
                                       cross_kv=(c["ck"], c["cv"]),
                                       kind=kind)
                new_cache[name] = c
            else:
                window = cfg.sliding_window if kind == "local" else None
                h, nk, nv = layer_decode(p, cfg, h, c["k"], c["v"], pos,
                                         window=window, kind=kind)
                new_cache[name] = {"k": nk, "v": nv}
        return h, new_cache

    h, new_cache = jax.lax.scan(body, h, (params["groups"], cache))
    h = L.norm(cfg, params["final_norm"], h)
    logits = L.unembed(params["embed"], cfg, h)
    return logits, new_cache
