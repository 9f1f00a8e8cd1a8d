"""Data pipeline, losses, checkpoint, serving, trainer integration."""
import os
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # optional dev dep: property tests skip, rest run
    given = settings = st = None

from repro.checkpoint.checkpoint import latest_step, restore, save
from repro.configs.base import ModelConfig
from repro.core import build_optimizer
from repro.data.synthetic import (ClassificationData, batch_iterator,
                                  lm_batch, two_view_batch)
from repro.models import get_model
from repro.obs import scopes
from repro.serving.decode import generate
from repro.training import losses
from repro.training.train_state import TrainState
from repro.training.trainer import (fit, make_classifier_step,
                                    make_ssl_step, make_train_step)


# ----- data -----

def test_classification_data_deterministic():
    d = ClassificationData(seed=3)
    x1, y1 = d.batch(jax.random.PRNGKey(0), 16)
    x2, y2 = d.batch(jax.random.PRNGKey(0), 16)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    x3, _ = d.batch(jax.random.PRNGKey(1), 16)
    assert not np.allclose(np.asarray(x1), np.asarray(x3))


def test_classification_data_learnable_snr():
    """Class means must be recoverable: nearest-mean classifier beats
    chance on clean eval data."""
    d = ClassificationData(num_classes=4, noise_scale=0.5, seed=0)
    x, y = d.eval_set(512)
    means = d.class_means()
    dists = jnp.sum((x[:, None] - means[None]) ** 2, axis=(2, 3, 4))
    acc = float(jnp.mean((jnp.argmin(dists, 1) == y)))
    assert acc > 0.9


def test_lm_batch_shapes_and_determinism():
    t1, l1 = lm_batch(jax.random.PRNGKey(0), 4, 32, 101)
    t2, l2 = lm_batch(jax.random.PRNGKey(0), 4, 32, 101)
    assert t1.shape == (4, 32) and l1.shape == (4, 32)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert int(t1.max()) < 101


def test_two_view_batch():
    d = ClassificationData()
    v1, v2 = two_view_batch(d, jax.random.PRNGKey(0), 8)
    assert v1.shape == v2.shape
    assert not np.allclose(np.asarray(v1), np.asarray(v2))


def test_batch_iterator_streams():
    d = ClassificationData()
    it = batch_iterator(d, 4)
    x1, _ = next(it)
    x2, _ = next(it)
    assert x1.shape == (4, 16, 16, 3)
    assert not np.allclose(np.asarray(x1), np.asarray(x2))


# ----- losses -----

def test_cross_entropy_matches_manual():
    logits = jnp.asarray([[1.0, 2.0, 0.5], [0.1, 0.2, 3.0]])
    labels = jnp.asarray([1, 2])
    manual = -np.mean([np.log(np.exp(2.0) / np.exp([1, 2, .5]).sum()),
                       np.log(np.exp(3.0) / np.exp([.1, .2, 3.]).sum())])
    np.testing.assert_allclose(float(losses.cross_entropy(logits, labels)),
                               manual, rtol=1e-6)


if st is not None:
    @settings(max_examples=50, deadline=None)
    @given(seed=st.integers(0, 10_000), b=st.integers(1, 4),
           s=st.sampled_from([4, 8]), v=st.sampled_from([16, 64]))
    def test_fused_ce_equals_reference(seed, b, s, v):
        rng = np.random.default_rng(seed)
        d = 12
        h = jnp.asarray(rng.normal(size=(b, s, d)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(d, v)) * 0.2, jnp.float32)
        y = jnp.asarray(rng.integers(0, v, (b, s)), jnp.int32)
        ref_val = losses.cross_entropy(h @ w, y)
        fused = losses.fused_ce_from_hidden(h, w, y)
        np.testing.assert_allclose(float(fused), float(ref_val), rtol=1e-5)
else:
    def test_fused_ce_equals_reference():
        pytest.importorskip("hypothesis")


def _ce_loss_and_grads(fn, h, w, y):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(fn, (0, 1))(h, w, y)


@pytest.mark.parametrize("b,s,v,dtype,shards", [
    # T = B·S < V: the vocabulary loop; d = 16, so T ≤ 16 keeps the logits
    (1, 8, 1000, "float32", 1),       # one chunk, logits kept
    (1, 8, 1000, "float32", 2),       # two shards of 500, logits kept
    (2, 300, 1000, "float32", 1),     # 2 chunks of 384 and a tail of 232
    (2, 300, 1000, "float32", 4),     # per shard of 250: 128 and 122
    (1, 700, 1187, "float32", 1),     # 2 chunks of 512 and a tail of 163
    (2, 300, 1187, "bfloat16", 1),
    # T ≥ V: the sequence loop
    (2, 512, 1000, "float32", 1),     # 2 chunks of CE_CHUNK
    (1, 1300, 1187, "float32", 1),    # S not a multiple of CE_CHUNK
    (2, 8, 12, "float32", 1),
])
def test_fused_ce_grads_equal_autodiff_of_reference(b, s, v, dtype, shards):
    """Loss, dh and dW of the fused CE against autodiff of
    ``cross_entropy(h @ w, y)`` in f32 from the same inputs.

    f32 cases agree to float rounding. The bf16 case rounds each logit,
    dlogit and head-gradient entry to bf16 (8 significant bits, relative
    error up to 2^-9 ≈ 2e-3) as the training step does: rounding errors
    of the logits mostly cancel in the mean loss (held to 1e-4
    relative), while a gradient entry, a sum of rounded terms, is off by
    up to about 2^-8 of its tensor's largest entry (held to 1e-2 of
    it)."""
    rng = np.random.default_rng(b * 7919 + s + v)
    d = 16
    h = jnp.asarray(rng.normal(size=(b, s, d)), dtype)
    w = jnp.asarray(rng.normal(size=(d, v)) * 0.2, dtype)
    y = jnp.asarray(rng.integers(0, v, (b, s)), jnp.int32)
    ref, ref_g = _ce_loss_and_grads(
        lambda h, w, y: losses.cross_entropy(h @ w, y),
        h.astype(jnp.float32), w.astype(jnp.float32), y)
    got, got_g = _ce_loss_and_grads(
        lambda h, w, y: losses.fused_ce_from_hidden(
            h, w, y, vocab_shards=shards), h, w, y)
    rtol, frac = (1e-5, 1e-5) if dtype == "float32" else (1e-4, 1e-2)
    np.testing.assert_allclose(float(got), float(ref), rtol=rtol)
    for g, r in zip(got_g, ref_g):
        assert g.dtype == jnp.dtype(dtype)
        r = np.asarray(r)
        np.testing.assert_allclose(np.asarray(g, np.float32), r,
                                   rtol=0, atol=frac * np.abs(r).max())


@pytest.mark.parametrize("tokens,vocab,split", [
    (2048, 151936, (19072, 7, 18432)),     # 151,936 = 128 · 1187
    (2048, 151936 // 4, (4864, 7, 3936)),  # one of 4 vocabulary shards
    (32768, 151936, (1280, 118, 896)),
    (600, 1000, (384, 2, 232)),
    (700, 1187, (512, 2, 163)),
    (8, 1000, (1000, 1, 0)),
    (2048, 4096, (512, 8, 0)),
])
def test_vocab_split_covers_the_vocabulary_once(tokens, vocab, split):
    width, full, tail = losses.vocab_split(tokens, vocab)
    assert (width, full, tail) == split
    assert full * width + tail == vocab and tail < width
    assert width % losses.LANES == 0 or width == vocab
    assert full + (tail > 0) <= -(-tokens // losses.CE_CHUNK)


def _loop_add_sizes(hlo_text: str) -> list[int]:
    """Element counts of every ``add`` inside a ``while`` body or a
    computation it calls: what a loop accumulates into."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    todo = [c for lines in comps.values() for line in lines
            for c in re.findall(r"body=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"(?:calls|to_apply|body)=%([\w.\-]+)", line)
    return [int(np.prod([int(x) for x in dims.split(",") if x]))
            for c in seen for line in comps[c]
            for dims in re.findall(r"= \w+\[([\d,]*)\]\S* add\(", line)]


@pytest.mark.parametrize("s,v,loop,matmuls", [
    (2048, 4096, "vocab", 4),    # T > D: the backward recomputes logits
    (64, 4096, "vocab", 3),      # T ≤ D: the forward's logits are kept
    (2048, 1024, "sequence", 4),
])
def test_fused_ce_backward_carries_no_head_gradient(s, v, loop, matmuls):
    """The compiled grad at (1, s, 64): with T < V no loop adds into a
    tensor of the head gradient's D·V elements, the head runs at most
    ``matmuls`` matmuls (forward, the backward's recompute where the
    logits are not kept, dh, dW) and every dot sits under
    ``head_loss``. The sequence loop (T ≥ V) does add into such a
    carry, which shows the check finds one."""
    b, d = 1, 64
    h = jax.ShapeDtypeStruct((b, s, d), jnp.float32)
    w = jax.ShapeDtypeStruct((d, v), jnp.float32)
    y = jax.ShapeDtypeStruct((b, s), jnp.int32)

    def loss(h, w, y):
        with jax.named_scope(scopes.HEAD_LOSS):
            return losses.fused_ce_from_hidden(h, w, y)

    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(h, w, y).compile()
    text = compiled.as_text()
    assert (d * v in _loop_add_sizes(text)) == (loop == "sequence")
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert cost["flops"] <= matmuls * 2 * b * s * d * v * 1.05
    dots = [line for line in text.splitlines()
            if re.search(r"= \S+ dot\(", line)]
    assert dots
    for line in dots:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert scopes.HEAD_LOSS in re.split(r"[/()]", op_name), op_name


def test_barlow_twins_identical_views_low_loss():
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    same = float(losses.barlow_twins_loss(z, z))
    diff = float(losses.barlow_twins_loss(
        z, jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)))
    assert same < diff


def test_accuracy():
    logits = jnp.asarray([[1.0, 0.0], [0.0, 1.0]])
    assert float(losses.accuracy(logits, jnp.asarray([0, 1]))) == 1.0
    assert float(losses.accuracy(logits, jnp.asarray([1, 0]))) == 0.0


# ----- checkpoint -----

def test_checkpoint_roundtrip():
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "ck")
        save(path, tree, step=7)
        assert latest_step(path) == 7
        out = restore(path, tree)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(out)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_checkpoint_shape_mismatch_raises():
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "ck")
        save(path, {"a": jnp.ones((2, 2))})
        with pytest.raises(ValueError):
            restore(path, {"a": jnp.ones((3, 3))})


def test_checkpoint_dtype_mismatch_raises():
    """A dtype-mismatched template must error, not silently mis-view."""
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "ck")
        save(path, {"a": jnp.ones((4, 4), jnp.float32)})
        with pytest.raises(ValueError, match="dtype"):
            restore(path, {"a": jnp.ones((4, 4), jnp.bfloat16)})


def test_checkpoint_leaf_count_mismatch_names_layouts():
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "ck")
        save(path, {"a": jnp.ones((2,)), "b": jnp.ones((2,))})
        with pytest.raises(ValueError, match="leaves"):
            restore(path, {"a": jnp.ones((2,))})


def test_checkpoint_fused_flat_opt_state_roundtrip():
    """Full TrainState round-trip on the fused path: bf16 params + flat
    (rows, 128) f32 momentum substrate buffers."""
    from repro.core.base import apply_updates
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(8, 16)), jnp.bfloat16),
              "scale": jnp.ones((16,), jnp.float32)}
    opt = build_optimizer("wa-lars", total_steps=5, learning_rate=0.1,
                          use_kernel="fused")
    state = TrainState.create(params, opt)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), p.dtype), params)
    # one real update so the flat momentum buffers are non-trivial
    updates, opt_state = opt.update(grads, state.opt_state, state.params)
    state = TrainState(state.step + 1, apply_updates(state.params, updates),
                       opt_state)
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "ck")
        save(path, state, step=1)
        assert latest_step(path) == 1
        out = restore(path, state)
        for a, b in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(out)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


# ----- trainer / serving integration -----

def _tiny_lm():
    cfg = ModelConfig(family="dense", num_layers=2, d_model=32, num_heads=2,
                      num_kv_heads=2, d_ff=64, vocab_size=64, remat=False)
    return cfg, get_model(cfg)


@pytest.mark.slow
def test_lm_training_reduces_loss():
    cfg, m = _tiny_lm()
    opt = build_optimizer("tvlars", total_steps=30, learning_rate=1.5)
    state = TrainState.create(m.init(jax.random.PRNGKey(0)), opt)
    step = make_train_step(m, opt)

    def batches():
        i = 0
        while True:
            t, y = lm_batch(jax.random.PRNGKey(i % 4), 8, 32, 64)
            yield {"tokens": t, "labels": y}
            i += 1

    state, hist = fit(step, state, batches(), 60)
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.97


def test_classifier_training_reaches_high_accuracy():
    from repro.models.cnn import apply_mlp_classifier, init_mlp_classifier
    data = ClassificationData(num_classes=4, noise_scale=0.6,
                              image_size=8, seed=1)
    params = init_mlp_classifier(jax.random.PRNGKey(0), in_dim=8 * 8 * 3,
                                 num_classes=4, hidden=64)
    opt = build_optimizer("wa-lars", total_steps=80, learning_rate=0.4)
    state = TrainState.create(params, opt)
    step = make_classifier_step(apply_mlp_classifier, opt)
    state, hist = fit(step, state, batch_iterator(data, 64), 80)
    assert hist[-1]["accuracy"] > 0.8


def test_ssl_training_runs():
    from repro.models.cnn import apply_mlp_classifier, init_mlp_classifier
    data = ClassificationData(num_classes=4, image_size=8)
    params = init_mlp_classifier(jax.random.PRNGKey(0), in_dim=8 * 8 * 3,
                                 num_classes=16, hidden=32)
    opt = build_optimizer("tvlars", total_steps=10, learning_rate=0.5)
    state = TrainState.create(params, opt)
    step = make_ssl_step(apply_mlp_classifier, opt)

    def views():
        i = 0
        while True:
            yield two_view_batch(data, jax.random.PRNGKey(i), 32)
            i += 1

    state, hist = fit(step, state, views(), 10)
    assert np.isfinite(hist[-1]["loss"])


def test_generate_greedy_deterministic():
    cfg, m = _tiny_lm()
    params = m.init(jax.random.PRNGKey(0))
    prompt = jnp.ones((2, 4), jnp.int32)
    o1 = generate(m, params, prompt, num_tokens=6)
    o2 = generate(m, params, prompt, num_tokens=6)
    assert o1.shape == (2, 6)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def test_norm_recorder_fig2_telemetry():
    from repro.core import NormRecorder
    cfg, m = _tiny_lm()
    opt = build_optimizer("nowa-lars", total_steps=10, learning_rate=0.5)
    state = TrainState.create(m.init(jax.random.PRNGKey(0)), opt)
    step = make_train_step(m, opt, record_norms=True)
    rec = NormRecorder(state.params)

    def batches():
        while True:
            t, y = lm_batch(jax.random.PRNGKey(0), 4, 16, 64)
            yield {"tokens": t, "labels": y}

    state, _ = fit(step, state, batches(), 10, recorder=rec)
    arrs = rec.as_arrays()
    assert arrs["lnr"].shape[0] == 10
    summ = rec.summary()
    assert np.isfinite(summ["max_initial_lnr"])
