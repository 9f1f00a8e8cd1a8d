"""Model registry: family -> (init, apply, init_cache, decode_step).

Unified functional API so the trainer / server / dry-run never branch on
architecture:

    model = get_model(cfg)
    params = model.init(rng)
    logits, aux = model.apply(params, batch)          # batch: dict
    cache = model.init_cache(params, batch_size, max_len, extra)
    logits, cache = model.decode_step(params, cache, tokens, pos)
    logits, cache = model.prefill(params, tokens, max_len, extra, lens)

``decode_step``'s ``pos`` is a scalar (all rows at the same depth) or a
[B] vector of per-row depths — the serving engine's continuous-batching
decode. Two ``ModelConfig`` knobs specialize the decode path without
changing this signature: ``use_decode_kernel`` routes each layer's
attention through the fused Pallas decode kernel
(``kernels.attention_decode``) and ``kv_cache_dtype`` sets the KV pool
storage dtype (``init_cache``/``prefill`` honor it; decode accumulates
in f32 either way). ``prefill`` is the single-shot batched prefill (one
full-sequence forward + KV-cache dump); it is ``None`` for families
without a batched-prefill lowering (ssm/hybrid/encdec fall back to the
token-by-token reference loop in ``repro.serving.decode``).

``batch["tokens"]`` [B,S] always; ``batch["extra_embeds"]`` carries the
stubbed modality frontend output (image patches for vlm, audio frames
for encdec) when the family needs it.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax

from repro.configs.base import ModelConfig
from repro.models import encdec as E
from repro.models import hybrid as H
from repro.models import layers
from repro.models import transformer as T
from repro.obs import scopes


class Model(NamedTuple):
    cfg: ModelConfig
    init: Callable
    apply: Callable           # (params, batch) -> (logits, aux)
    init_cache: Callable      # (params, batch, max_len, extra) -> cache
    decode_step: Callable     # (params, cache, tokens, pos) -> (logits, cache)
    loss: Callable            # (params, batch) -> (mean CE, aux) — fused
                              # chunked CE head, never materialises logits
    prefill: Optional[Callable] = None
                              # (params, tokens, max_len, extra) ->
                              # (logits [B,S,V], cache); None = family
                              # has no batched-prefill lowering


def _needs_extra(cfg: ModelConfig) -> bool:
    return cfg.family in ("vlm", "encdec")


def extra_embed_shape(cfg: ModelConfig, batch: int) -> Optional[tuple]:
    if cfg.family == "vlm":
        return (batch, cfg.num_image_tokens, cfg.d_model)
    if cfg.family == "encdec":
        return (batch, cfg.encoder_seq, cfg.d_model)
    return None


def get_model(cfg: ModelConfig) -> Model:
    prefill_fn = None
    if cfg.family in ("dense", "moe", "vlm"):
        init_fn, apply_fn = T.init_lm, T.apply_lm
        hidden_fn = T.apply_lm_hidden
        cache_fn, decode_fn = T.init_lm_cache, T.decode_lm
        prefill_fn = T.apply_lm_prefill
    elif cfg.family == "ssm":
        init_fn, apply_fn = H.init_ssm_lm, H.apply_ssm_lm
        hidden_fn = H.apply_ssm_lm_hidden
        cache_fn, decode_fn = H.init_ssm_cache, H.decode_ssm_lm
    elif cfg.family == "hybrid":
        init_fn, apply_fn = H.init_hybrid_lm, H.apply_hybrid_lm
        hidden_fn = H.apply_hybrid_lm_hidden
        cache_fn, decode_fn = H.init_hybrid_cache, H.decode_hybrid_lm
    elif cfg.family == "encdec":
        init_fn, apply_fn = E.init_encdec, E.apply_encdec
        hidden_fn = E.apply_encdec_hidden
        cache_fn, decode_fn = E.init_encdec_cache, E.decode_encdec
    else:
        raise ValueError(f"unknown family {cfg.family!r}")

    def init(rng):
        return init_fn(cfg, rng)

    def _extra(batch):
        return batch.get("extra_embeds") if _needs_extra(cfg) else None

    def apply(params, batch: dict):
        return apply_fn(cfg, params, batch["tokens"], _extra(batch))

    def loss(params, batch: dict):
        from repro.training import losses
        h, aux = hidden_fn(cfg, params, batch["tokens"], _extra(batch))
        with jax.named_scope(scopes.HEAD_LOSS):
            emb = params["embed"]
            w = emb["table"].T if cfg.tie_embeddings else emb["head"]
            ce = losses.fused_ce_from_hidden(
                h, w.astype(h.dtype), batch["labels"],
                vocab_shards=layers.vocab_shards(w.shape[1]))
        return ce, aux

    def init_cache(params, batch_size: int, max_len: int, extra=None):
        return cache_fn(cfg, params, batch_size, max_len, extra)

    def decode_step(params, cache, tokens, pos):
        return decode_fn(cfg, params, cache, tokens, pos)

    prefill = None
    if prefill_fn is not None:
        def prefill(params, tokens, max_len, extra=None, lens=None):
            return prefill_fn(cfg, params, tokens, max_len, extra,
                              lens)

    return Model(cfg, init, apply, init_cache, decode_step, loss,
                 prefill)
