"""Per-architecture smoke tests (reduced configs) + model-math oracles.

Every assigned architecture instantiates its REDUCED same-family variant
(2-5 layers, d_model<=512, <=4 experts), runs one forward and one train
step on CPU, and asserts output shapes + no NaNs. Decode paths are
checked against the full forward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import build_optimizer
from repro.data.synthetic import lm_batch
from repro.models import extra_embed_shape, get_model
from repro.training.train_state import TrainState
from repro.training.trainer import make_train_step


def _batch(cfg, b, s, rng_seed=0):
    toks, labels = lm_batch(jax.random.PRNGKey(rng_seed), b, s,
                            cfg.vocab_size)
    batch = {"tokens": toks, "labels": labels}
    es = extra_embed_shape(cfg, b)
    if es is not None:
        batch["extra_embeds"] = jnp.asarray(
            np.random.default_rng(1).normal(size=es) * 0.1, jnp.float32)
    return batch


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_forward_shapes_and_no_nan(arch_id):
    cfg = get_smoke_config(arch_id)
    assert cfg.d_model <= 512 and cfg.num_layers <= 5
    assert cfg.num_experts <= 4
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b, s = 2, 16
    logits, aux = m.apply(params, _batch(cfg, b, s))
    assert logits.shape == (b, s, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    assert np.isfinite(float(aux.load_balance_loss))


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_one_train_step(arch_id):
    cfg = get_smoke_config(arch_id).replace(remat=True)
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    opt = build_optimizer("tvlars", total_steps=10, learning_rate=1.0)
    state = TrainState.create(params, opt)
    step = jax.jit(make_train_step(m, opt))
    state, metrics = step(state, _batch(cfg, 2, 16))
    assert np.isfinite(float(metrics["loss"]))
    assert int(state.step) == 1


@pytest.mark.slow          # ~2 min across the arch grid: full-CI lane
@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_decode_matches_full_forward(arch_id):
    cfg = get_smoke_config(arch_id)
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(2))
    b, s = 2, 8
    batch = _batch(cfg, b, s, rng_seed=3)
    full, _ = m.apply(params, batch)
    cache = m.init_cache(params, b, s, batch.get("extra_embeds"))
    outs = []
    for t in range(s):
        lg, cache = m.decode_step(params, cache,
                                  batch["tokens"][:, t:t + 1], jnp.int32(t))
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                               rtol=3e-2, atol=3e-3)


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_full_config_matches_assignment(arch_id):
    """The full configs carry the exact published numbers."""
    cfg = get_config(arch_id)
    expected = {
        "llama-3.2-vision-11b": (40, 4096, 32, 8, 14336, 128256),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "mamba2-1.3b": (48, 2048, None, None, 0, 50280),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "codeqwen1.5-7b": (32, 4096, 32, 32, 13440, 92416),
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
        "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
    }[arch_id]
    layers, d, h, kv, ff, v = expected
    assert cfg.num_layers == layers and cfg.d_model == d
    assert cfg.d_ff == ff and cfg.vocab_size == v
    if h is not None:
        assert cfg.num_heads == h and cfg.num_kv_heads == kv
    if arch_id == "mamba2-1.3b":
        assert cfg.ssm_state == 128
    if arch_id == "zamba2-1.2b":
        assert cfg.ssm_state == 64 and cfg.attn_every == 6
    if arch_id == "qwen3-moe-30b-a3b":
        assert cfg.num_experts == 128 and cfg.experts_per_token == 8
    if arch_id == "olmoe-1b-7b":
        assert cfg.num_experts == 64 and cfg.experts_per_token == 8
    if arch_id == "gemma3-12b":
        assert cfg.sliding_window == 1024 and cfg.global_every == 6
    if arch_id == "qwen2.5-3b":
        assert cfg.source == "hf:Qwen/Qwen2.5-3B" and cfg.tie_embeddings
    if arch_id == "whisper-large-v3":
        assert cfg.encoder_layers == 32 and cfg.encoder_seq == 1500


def test_ssd_chunked_matches_naive_recurrence():
    from repro.models.ssm import _ssd_chunked
    rng = np.random.default_rng(0)
    b, s, h, p, n = 2, 16, 3, 4, 5
    xh = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(b, s, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)

    y_ref = np.zeros((b, s, h, p), np.float32)
    for bi in range(b):
        state = np.zeros((h, p, n), np.float32)
        for t in range(s):
            da = np.exp(np.asarray(dt)[bi, t] * np.asarray(a))
            state = state * da[:, None, None] + np.einsum(
                "h,hp,n->hpn", np.asarray(dt)[bi, t],
                np.asarray(xh)[bi, t], np.asarray(B)[bi, t])
            y_ref[bi, t] = np.einsum("hpn,n->hp", state,
                                     np.asarray(C)[bi, t])
    for chunk in (4, 8, 16):
        y = _ssd_chunked(xh, dt, a, B, C, chunk)
        np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4,
                                   atol=1e-5)


def test_moe_matches_dense_reference():
    from repro.configs.base import ModelConfig
    from repro.models.moe import init_moe, moe_apply
    cfg = ModelConfig(family="moe", num_layers=2, d_model=32, d_ff=16,
                      num_experts=4, experts_per_token=2,
                      capacity_factor=8.0, vocab_size=64)
    params = init_moe(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 8, 32)), jnp.float32)
    out, _ = moe_apply(params, cfg, x)
    logits = x @ params["router"]
    tp, ti = jax.lax.top_k(jax.nn.softmax(logits, -1), 2)
    tp = tp / tp.sum(-1, keepdims=True)
    ref_out = np.zeros_like(np.asarray(x))
    for bi in range(3):
        for si in range(8):
            for kk in range(2):
                e = int(ti[bi, si, kk])
                xx = np.asarray(x)[bi, si]
                hh = xx @ np.asarray(params["wi"])[e]
                gg = xx @ np.asarray(params["wg"])[e]
                act = (gg / (1 + np.exp(-gg))) * hh
                ref_out[bi, si] += float(tp[bi, si, kk]) * (
                    act @ np.asarray(params["wo"])[e])
    np.testing.assert_allclose(np.asarray(out), ref_out, rtol=1e-4,
                               atol=1e-5)


def test_moe_capacity_drops_overflow():
    from repro.configs.base import ModelConfig
    from repro.models.moe import moe_capacity
    cfg = ModelConfig(num_experts=4, experts_per_token=2,
                      capacity_factor=1.0)
    assert moe_capacity(16, cfg) == 8
    cfg2 = ModelConfig(num_experts=128, experts_per_token=8,
                       capacity_factor=1.25)
    assert moe_capacity(4096, cfg2) == 320


def test_chunked_attention_matches_full():
    from repro.models import layers as L
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 16, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
    ref_out = L.gqa_scores_apply(q, k, v, ("causal", None))
    old = L.Q_CHUNK
    try:
        L.Q_CHUNK = 4
        out = L.gqa_scores_apply(q, k, v, ("causal", None))
    finally:
        L.Q_CHUNK = old
    np.testing.assert_allclose(np.asarray(ref_out), np.asarray(out),
                               rtol=1e-5, atol=1e-6)


def test_sliding_window_mask_limits_context():
    from repro.models import layers as L
    # token far past the window must not attend to token 0
    q = jnp.ones((1, 12, 1, 4))
    k = jnp.ones((1, 12, 1, 4))
    v = jnp.concatenate([jnp.full((1, 1, 1, 4), 100.0),
                         jnp.zeros((1, 11, 1, 4))], axis=1)
    out = L.gqa_scores_apply(q, k, v, ("causal", 3))
    # last position attends only within window of 3 -> no 100s leak
    assert float(out[0, -1].max()) < 1.0


def test_cnn_inits_and_forward():
    from repro.models.cnn import INITS, apply_cnn, init_cnn
    x = jnp.ones((2, 16, 16, 3))
    for method in INITS:
        p = init_cnn(jax.random.PRNGKey(0), num_classes=10, width=8,
                     init_method=method)
        logits = apply_cnn(p, x)
        assert logits.shape == (2, 10)
        assert np.isfinite(np.asarray(logits)).all()


def test_windowed_chunked_scores_match_unchunked():
    """The query-chunk scan on a sliding-window layer equals one pass."""
    from repro.models import layers as L
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 64, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 64, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 64, 2, 8)), jnp.float32)
    old = L.Q_CHUNK
    try:
        L.Q_CHUNK = 64
        ref = L.gqa_scores_apply(q, k, v, ("causal", 8))
        L.Q_CHUNK = 8
        out = L.gqa_scores_apply(q, k, v, ("causal", 8))
    finally:
        L.Q_CHUNK = old
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-6)
