"""Serving-engine tier (``-m serving``): the continuous-batching
correctness contracts.

* engine == per-request ``generate`` token-for-token under greedy
  sampling with STAGGERED arrivals (dense + windowed gemma3; MoE at
  bucket-aligned prompt lengths — capacity routing makes token drops a
  function of the padded sequence length, so parity requires the
  engine's pow2 padding to be the identity),
* batched single-shot prefill == the token-by-token reference loop
  (the oracle kept in ``serving.prefill_reference``),
* ZERO decode-step recompiles across every occupancy transition
  (admit / evict / finish / re-admit),
* KV pages are reused after eviction and stale tenants never leak into
  a successor's tokens,
* weights restored through the sharding-aware ``checkpoint.restore``
  (``Engine.from_checkpoint``, with and without a mesh) serve
  identically to the in-memory params.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serving
from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.kernels import ref as kernel_ref
from repro.kernels.attention_decode import attention_decode_pallas
from repro.models import get_model, layers

pytestmark = pytest.mark.serving


def _model(arch="qwen2.5-3b"):
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, size=ln).astype(np.int32)
            for ln in lens]


def _reference(model, params, prompt, n, max_len):
    out = serving.generate(model, params, jnp.asarray(prompt[None]),
                           num_tokens=n, max_len=max_len)
    return [int(x) for x in np.asarray(out)[0]]


def _run_staggered(eng, prompts, new, arrive):
    """Submit per the arrival schedule {step: [idx]}, step to drain."""
    ids, results = {}, {}
    t = 0
    while len(results) < len(prompts):
        for i in arrive.get(t, []):
            ids[i] = eng.submit(prompts[i], max_new_tokens=new[i])
        for r in eng.step():
            results[r.id] = r
        t += 1
        assert t < 10_000, "engine failed to drain"
    return {i: results[rid].tokens for i, rid in ids.items()}


# -- continuous batching == per-request generate --------------------------

@pytest.mark.parametrize("arch", ["qwen2.5-3b", "gemma3-12b"])
def test_engine_matches_generate_staggered(arch):
    cfg, model, params = _model(arch)
    sc = serving.ServeConfig(slots=3, max_len=64, page_size=8,
                             prefill_batch=2)
    eng = serving.Engine(model, params, sc)
    prompts = _prompts(cfg, (5, 9, 3, 12, 7))
    new = [6, 4, 8, 5, 7]
    got = _run_staggered(eng, prompts, new,
                         {0: [0, 1], 2: [2, 3], 5: [4]})
    for i, p in enumerate(prompts):
        want = _reference(model, params, p, new[i], sc.max_len)
        assert got[i] == want, f"req {i}: {got[i]} != {want}"


def test_engine_matches_generate_moe_bucket_aligned():
    """MoE capacity routing drops tokens as a function of the PADDED
    length — parity holds when prompts already sit on the engine's
    pow2/page buckets (here: every prompt exactly 8 = page_size)."""
    cfg, model, params = _model("olmoe-1b-7b")
    sc = serving.ServeConfig(slots=2, max_len=32, page_size=8,
                             prefill_batch=2)
    eng = serving.Engine(model, params, sc)
    prompts = _prompts(cfg, (8, 8, 8))
    new = [5, 5, 5]
    got = _run_staggered(eng, prompts, new, {0: [0, 1], 3: [2]})
    for i, p in enumerate(prompts):
        want = _reference(model, params, p, new[i], sc.max_len)
        assert got[i] == want, f"moe req {i}: {got[i]} != {want}"


# -- batched prefill == token-by-token oracle -----------------------------

@pytest.mark.parametrize("arch", ["qwen2.5-3b", "gemma3-12b"])
def test_batched_prefill_matches_reference_loop(arch):
    cfg, model, params = _model(arch)
    tokens = jnp.asarray(_prompts(cfg, (11, 11), seed=3))
    max_len = 32
    fast_logits, fast_cache = serving.prefill(model, params, tokens,
                                              max_len)
    ref_logits, ref_cache = serving.prefill_reference(model, params,
                                                      tokens, max_len)
    np.testing.assert_allclose(np.asarray(fast_logits),
                               np.asarray(ref_logits), atol=1e-5)
    # the caches must agree wherever the reference wrote (the decode
    # masks everything beyond the prompt, so compare through decode)
    tok = jnp.argmax(fast_logits[:, -1:], -1).astype(jnp.int32)
    fast_next, _ = model.decode_step(params, fast_cache, tok,
                                     jnp.int32(11))
    ref_next, _ = model.decode_step(params, ref_cache, tok,
                                    jnp.int32(11))
    np.testing.assert_allclose(np.asarray(fast_next),
                               np.asarray(ref_next), atol=1e-5)


def test_prefill_is_single_shot():
    """The batched path must not loop over sequence positions: one
    jit'd call, whose trace count does not scale with prompt length."""
    cfg, model, params = _model()
    calls = 0
    inner = model.prefill

    def counting(params, tokens, max_len, extra=None):
        nonlocal calls
        calls += 1
        return inner(params, tokens, max_len, extra)

    model = model._replace(prefill=counting)
    tokens = jnp.asarray(_prompts(cfg, (13, 13), seed=5))
    serving.prefill(model, params, tokens, 32)
    assert calls == 1


# -- compile-once decode --------------------------------------------------

def test_zero_decode_recompiles_across_occupancy():
    cfg, model, params = _model()
    sc = serving.ServeConfig(slots=2, max_len=32, page_size=8,
                             prefill_batch=2)
    eng = serving.Engine(model, params, sc)
    prompts = _prompts(cfg, (4, 6, 5, 7))
    # phase 1: fill both slots
    a = eng.submit(prompts[0], max_new_tokens=4)
    b = eng.submit(prompts[1], max_new_tokens=9)
    eng.step()
    # phase 2: evict one mid-flight, admit another into the freed slot
    eng.evict(a)
    c = eng.submit(prompts[2], max_new_tokens=3)
    eng.step()
    # phase 3: natural finishes, then a fresh admit into an empty engine
    eng.drain()
    d = eng.submit(prompts[3], max_new_tokens=2)
    eng.drain()
    assert {b, c, d} <= set(eng._results)
    assert eng.decode_compilations == 1, eng.stats()


# -- paged KV reuse -------------------------------------------------------

def test_page_reuse_after_eviction():
    cfg, model, params = _model()
    sc = serving.ServeConfig(slots=2, max_len=32, page_size=8,
                             prefill_batch=2)
    eng = serving.Engine(model, params, sc)
    prompts = _prompts(cfg, (12, 12, 12))

    rid = eng.submit(prompts[0], max_new_tokens=8)
    for _ in range(3):
        eng.step()
    pages_live = eng._kv.table.pages_used()
    assert pages_live >= 2                       # 12 tokens, 8/page
    eng.evict(rid)
    assert eng._kv.table.pages_used() == 0
    assert eng._kv.table.free_pages == eng._kv.table.total_pages

    # the successor reuses the freed pages (no new allocation region)
    before = eng._kv.table.reused_pages
    eng.submit(prompts[1], max_new_tokens=4)
    eng.drain()
    assert eng._kv.table.reused_pages > before

    # and serves exactly what a fresh engine would (stale KV unreachable)
    fresh = serving.Engine(model, params, sc)
    r2 = fresh.submit(prompts[2], max_new_tokens=6)
    fresh.drain()
    r1 = eng.submit(prompts[2], max_new_tokens=6)
    eng.drain()
    assert eng.result(r1).tokens == fresh.result(r2).tokens


def test_page_table_accounting():
    t = serving.PageTable(slots=2, pages_per_slot=4, page_size=8)
    assert t.ensure(0, 12) == [0, 1]
    assert t.ensure(0, 13) == []                 # still page 1
    assert t.ensure(0, 17) == [2]
    assert t.pages_used(0) == 3 and t.free_pages == 5
    with pytest.raises(ValueError):
        t.ensure(1, 33)                          # beyond the slot
    assert t.release(0) == [0, 1, 2]
    assert t.ensure(0, 9) == [0, 1] and t.reused_pages == 2


# -- config validation ----------------------------------------------------

def test_serve_config_validation():
    with pytest.raises(ValueError):
        serving.ServeConfig(max_len=30, page_size=16)
    with pytest.raises(ValueError):
        serving.ServeConfig(slots=0)
    with pytest.raises(ValueError):
        serving.SamplingParams(temperature=-1.0)
    cfg, model, params = _model()
    eng = serving.Engine(model, params, serving.ServeConfig(
        slots=1, max_len=32, page_size=8))
    with pytest.raises(ValueError):
        eng.submit(np.arange(30), max_new_tokens=8)   # exceeds max_len
    with pytest.raises(ValueError):
        eng.submit([], max_new_tokens=1)


def test_engine_rejects_families_without_prefill():
    cfg = get_smoke_config("mamba2-1.3b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="prefill"):
        serving.Engine(model, params, serving.ServeConfig())


def _serve_some(eng, prompts, new=4):
    ids = [eng.submit(p, max_new_tokens=new) for p in prompts]
    eng.drain()
    return [eng.result(i).tokens for i in ids]


# -- fused decode kernel: kernel == oracle == jnp -------------------------

def _decode_operands(b, t, h, hkv, dh, cache_dtype, pos, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, 1, h, dh), jnp.float32)
    nk = jnp.asarray(rng.randn(b, 1, hkv, dh), jnp.float32)
    nv = jnp.asarray(rng.randn(b, 1, hkv, dh), jnp.float32)
    kc = jnp.asarray(rng.randn(b, t, hkv * dh)).astype(cache_dtype)
    vc = jnp.asarray(rng.randn(b, t, hkv * dh)).astype(cache_dtype)
    return q, nk, nv, kc, vc, jnp.asarray(pos, jnp.int32)


# rows of the 4-block cases: a free slot (pos 0), live prefixes ending
# on a block's last row, on the next block's first row and mid-block,
# pos T-1; the ring adds rows past the wrap (all of it live)
_BLOCK_ROWS = [0, 15, 16, 37, 62, 63]
_RING_ROWS = [0, 15, 37, 63, 64, 79, 100, 200]


@pytest.mark.parametrize("h,hkv", [(4, 2), (4, 1), (2, 2)])
@pytest.mark.parametrize("window,t,block_t", [
    (None, 32, None), (8, 8, None), (None, 64, 16), (64, 64, 16)],
    ids=["None", "8", "None-4blocks", "64-4blocks"])
@pytest.mark.parametrize("cache_dtype", ["float32", "bfloat16"])
def test_decode_kernel_matches_oracle(h, hkv, window, t, block_t,
                                      cache_dtype):
    """Op-level three-way parity across GQA/MQA/MHA layouts, global and
    ring-buffer layers, f32 and bf16 pools.  Windowed rows sit several
    multiples past the window (deep wrap). The 4-block cases run the
    live-block clamp: each row reads only up to its last live block,
    and the ring reads all of it once wrapped. Caches must be bitwise
    identical (same single-row append), outputs within the documented
    tolerance."""
    dt = jnp.dtype(cache_dtype)
    if block_t:
        pos = _RING_ROWS if window else _BLOCK_ROWS
    else:
        pos = [0, 9, 30] if window else [0, 5, 31]
    operands = _decode_operands(len(pos), t, h, hkv, 16, dt, pos)
    if block_t:
        o1, k1, v1 = attention_decode_pallas(
            *operands, window=window, interpret=True, _block_t=block_t)
    else:
        o1, k1, v1 = kernel_ops.attention_decode_fused(*operands,
                                                       window=window)
    o2, k2, v2 = kernel_ref.ref_attention_decode(*operands,
                                                 window=window)
    tol = kernel_ref.decode_parity_tolerance(dt)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), **tol)
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_layer_decode_kernel_matches_jnp():
    """``attention_decode(use_kernel=True)`` == the jnp path through
    the full layer (projections + RoPE shared): windowed vector pos
    with deep wrap, global vector pos, and scalar pos."""
    cfg = ModelConfig(d_model=64, num_heads=4, num_kv_heads=2,
                      head_dim=16)
    params = layers.init_attention(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(3, 1, 64), jnp.float32)
    tol = kernel_ref.decode_parity_tolerance(jnp.float32)
    cases = [(None, 32, jnp.asarray([0, 5, 31], jnp.int32)),
             (8, 8, jnp.asarray([2, 29, 17], jnp.int32)),
             (8, 8, jnp.int32(19))]
    for window, t, pos in cases:
        kc = jnp.asarray(rng.randn(3, t, 2 * 16), jnp.float32)
        vc = jnp.asarray(rng.randn(3, t, 2 * 16), jnp.float32)
        o1, k1, v1 = layers.attention_decode(params, cfg, x, kc, vc,
                                             pos, window=window,
                                             use_kernel=True)
        o2, k2, v2 = layers.attention_decode(params, cfg, x, kc, vc,
                                             pos, window=window,
                                             use_kernel=False)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   **tol)
        np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_bf16_cache_decode_accumulates_f32():
    """A bf16 KV pool must still contract and softmax in f32: decode
    against a bf16 pool stays within bf16 resolution of the f32-pool
    result (would blow past the tolerance if scores accumulated in
    bf16)."""
    cfg = ModelConfig(d_model=64, num_heads=4, num_kv_heads=2,
                      head_dim=16)
    params = layers.init_attention(cfg, jax.random.PRNGKey(2))
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 1, 64), jnp.float32)
    kc = jnp.asarray(rng.randn(2, 32, 2 * 16), jnp.float32)
    vc = jnp.asarray(rng.randn(2, 32, 2 * 16), jnp.float32)
    pos = jnp.asarray([12, 31], jnp.int32)
    tol = kernel_ref.decode_parity_tolerance(jnp.bfloat16)
    want, _, _ = layers.attention_decode(params, cfg, x, kc, vc, pos,
                                         use_kernel=False)
    for use_kernel in (False, True):
        got, _, _ = layers.attention_decode(
            params, cfg, x, kc.astype(jnp.bfloat16),
            vc.astype(jnp.bfloat16), pos, use_kernel=use_kernel)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol)


def test_engine_windowed_wraparound_staggered():
    """jnp-path engine parity with positions several multiples past the
    sliding window: staggered arrivals make each slot's ring buffer
    wrap at a different step (gemma3 smoke window=8, depths reach
    ~4x window)."""
    cfg, model, params = _model("gemma3-12b")
    sc = serving.ServeConfig(slots=3, max_len=64, page_size=8,
                             prefill_batch=2)
    eng = serving.Engine(model, params, sc)
    prompts = _prompts(cfg, (5, 9, 3), seed=7)
    new = [25, 20, 30]
    got = _run_staggered(eng, prompts, new, {0: [0, 1], 4: [2]})
    for i, p in enumerate(prompts):
        want = _reference(model, params, p, new[i], sc.max_len)
        assert got[i] == want, f"req {i}: {got[i]} != {want}"


def test_engine_kernel_matches_generate_staggered():
    """Engine with the fused kernel == per-request jnp ``generate``
    token-for-token (greedy), staggered arrivals, depths past 3x the
    gemma3 window so both global and wrapped ring layers are hit."""
    cfg, model, params = _model("gemma3-12b")
    sc = serving.ServeConfig(slots=3, max_len=64, page_size=8,
                             prefill_batch=2, use_kernel=True)
    eng = serving.Engine(model, params, sc)
    prompts = _prompts(cfg, (5, 9, 3, 7), seed=11)
    new = [22, 18, 25, 20]
    got = _run_staggered(eng, prompts, new, {0: [0, 1], 3: [2, 3]})
    for i, p in enumerate(prompts):
        want = _reference(model, params, p, new[i], sc.max_len)
        assert got[i] == want, f"kernel req {i}: {got[i]} != {want}"


def test_engine_kernel_zero_decode_recompiles():
    """The fused kernel keys on the fixed [slots, max_len] pool: one
    decode compilation across admit / evict / finish / re-admit."""
    cfg, model, params = _model("gemma3-12b")
    sc = serving.ServeConfig(slots=2, max_len=32, page_size=8,
                             prefill_batch=2, use_kernel=True)
    eng = serving.Engine(model, params, sc)
    prompts = _prompts(cfg, (4, 6, 5, 7), seed=13)
    a = eng.submit(prompts[0], max_new_tokens=4)
    eng.submit(prompts[1], max_new_tokens=9)
    eng.step()
    eng.evict(a)
    eng.submit(prompts[2], max_new_tokens=3)
    eng.step()
    eng.drain()
    eng.submit(prompts[3], max_new_tokens=2)
    eng.drain()
    assert eng.decode_compilations == 1, eng.stats()


def test_engine_kernel_bf16_cache_matches_jnp():
    """bf16 KV pool: kernel path and jnp path sample identical greedy
    tokens (both read the same bf16 values, both accumulate in f32)."""
    cfg, model, params = _model("gemma3-12b")
    kw = dict(slots=2, max_len=32, page_size=8, prefill_batch=2,
              cache_dtype="bfloat16")
    prompts = _prompts(cfg, (6, 9), seed=17)
    want = _serve_some(serving.Engine(
        model, params, serving.ServeConfig(**kw)), prompts, new=12)
    got = _serve_some(serving.Engine(
        model, params, serving.ServeConfig(**kw, use_kernel=True)),
        prompts, new=12)
    assert got == want


def test_engine_counts_live_kv_blocks(monkeypatch):
    """``decode_kv_blocks_read`` sums, over decode steps, layers and
    slots, the blocks the kernel reads (``min(pos, T-1) // block_t +
    1``), and each ``decode`` span carries the step's count. A 2 KiB
    tile budget cuts the 64-row global pools of 64 f32 lanes into 8
    blocks of 8 rows; the 8-row rings stay one block. The tokens stay
    those of the jnp ``generate``."""
    from repro.kernels import attention_decode
    from repro.obs.trace import Tracer
    monkeypatch.setattr(attention_decode, "TILE_BYTES", 2048)
    cfg, model, params = _model("gemma3-12b")
    tracer = Tracer()
    eng = serving.Engine(model, params, serving.ServeConfig(
        slots=3, max_len=64, page_size=8, prefill_batch=4,
        use_kernel=True), tracer=tracer)
    lens, new = (5, 20, 11), (12, 30, 7)
    prompts = _prompts(cfg, lens, seed=23)
    ids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
    eng.drain()
    for rid, p, n in zip(ids, prompts, new):
        assert eng.result(rid).tokens == _reference(model, params, p, n, 64)
    # slot s holds request s (admitted together at step 0); it decodes
    # at depths lens[s] .. lens[s] + new[s] - 2, then sits free at 0
    pools = [(2, 8, 8), (2, 64, 8)]       # (layers, T, block_t)
    per_step = []
    for k in range(max(new) - 1):
        pos = [ln + k if k <= n - 2 else 0 for ln, n in zip(lens, new)]
        per_step.append(sum(g * (min(p, t - 1) // bt + 1)
                            for g, t, bt in pools for p in pos))
    spans = [r["kv_blocks"] for r in tracer.events()
             if r["kind"] == "span" and r["name"] == "decode"]
    assert spans == per_step
    stats = eng.stats()
    assert stats["decode_kv_blocks_read"] == sum(per_step)
    assert stats["decode_kv_blocks_pool"] == len(per_step) * 3 * (2 + 16)
    assert eng.decode_compilations == 1


def test_serve_config_rejects_bad_cache_dtype():
    with pytest.raises(ValueError, match="cache_dtype"):
        serving.ServeConfig(cache_dtype="float7")


# -- checkpoint restore ---------------------------------------------------

def test_mesh_restored_weights_serve_identically(tmp_path):
    from repro import checkpoint
    cfg, model, params = _model()
    path = str(tmp_path / "ckpt")
    checkpoint.save(path, params, step=0)
    sc = serving.ServeConfig(slots=2, max_len=32, page_size=8)
    prompts = _prompts(cfg, (6, 9))

    want = _serve_some(serving.Engine(model, params, sc), prompts)
    flat = _serve_some(serving.Engine.from_checkpoint(path, model, sc),
                       prompts)
    assert flat == want

    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    eng = serving.Engine.from_checkpoint(path, model, sc, mesh=mesh)
    assert _serve_some(eng, prompts) == want
    leaf = jax.tree_util.tree_leaves(eng.params)[0]
    assert isinstance(leaf.sharding, jax.sharding.NamedSharding)
