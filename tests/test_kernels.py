"""The kernels the chip runs, and the model's norm, against plain math.

The fused layer-wise step (``kernels.segmented_update``) on single
leaves of awkward shapes against the jnp tree path; the decode kernel's
``REPRO_FORCE_REF`` dispatch; ``models.layers.rmsnorm`` against the
float32 formula.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apply_updates, labels, lars, schedules
from repro.kernels import ops, ref
from repro.models import layers

SHAPES = [(8,), (7,), (128,), (129,), (33, 65), (256, 128), (512, 513),
          (3, 5, 130), (2, 2, 2, 17)]


def _two_lars_steps(w, grads, use_kernel, base_lr, **hyper):
    """Two heavy-ball LARS steps on the one-leaf tree ``{"w": w}``,
    labelled ADAPT whatever its rank -> (params, last update)."""
    opt = lars(schedules.constant(base_lr), momentum=0.9,
               param_labels={"w": labels.ADAPT}, use_kernel=use_kernel,
               **hyper)
    params = {"w": w}
    state = opt.init(params)
    for g in grads:
        u, state = opt.update({"w": g}, state, params)
        params = apply_updates(params, u)
    return params["w"], u["w"]


def _lars_problem(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=shape), dtype)
    grads = [jnp.asarray(rng.normal(size=shape), dtype) for _ in range(2)]
    return w, grads


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_single_leaf_matches_tree_path(shape, dtype):
    """One segment of every awkward size: lane padding (7, 129, 513
    elements to a row), row tails and a multi-block grid (512 x 513)."""
    w, grads = _lars_problem(shape, dtype, int(np.prod(shape)))
    kw = dict(base_lr=0.15, eta=1e-3, weight_decay=5e-4)
    p1, u1 = _two_lars_steps(w, grads, "fused", **kw)
    p2, u2 = _two_lars_steps(w, grads, False, **kw)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(u1), np.asarray(u2),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(p1, np.float32),
                               np.asarray(p2, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("nesterov", [False, True])
def test_fused_single_leaf_nesterov(nesterov):
    w, grads = _lars_problem((64, 32), jnp.float32, 0)
    kw = dict(base_lr=0.1, eta=1e-3, weight_decay=1e-4, nesterov=nesterov)
    _, u1 = _two_lars_steps(w, grads, "fused", **kw)
    _, u2 = _two_lars_steps(w, grads, False, **kw)
    np.testing.assert_allclose(np.asarray(u1), np.asarray(u2), rtol=1e-5,
                               atol=1e-6)


def _rmsnorm_f32(x, w, eps=1e-6):
    x = np.asarray(x, np.float32)
    ms = np.mean(np.square(x), axis=-1, keepdims=True)
    return x / np.sqrt(ms + eps) * (1.0 + np.asarray(w, np.float32))


@pytest.mark.parametrize("rows,d", [(1, 128), (4, 256), (17, 384),
                                    (64, 512), (3, 3 * 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_model_rmsnorm_matches_f32_formula(rows, d, dtype):
    rng = np.random.default_rng(rows * d)
    x = jnp.asarray(rng.normal(size=(rows, d)), dtype)
    w = jnp.asarray(rng.normal(size=(d,)) * 0.1, jnp.float32)
    y = layers.rmsnorm({"scale": w}, x)
    assert y.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               _rmsnorm_f32(x, w), rtol=tol, atol=tol)


def test_model_rmsnorm_batched_rank3():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 9, 256)), jnp.float32)
    w = jnp.zeros((256,))
    np.testing.assert_allclose(np.asarray(layers.rmsnorm({"scale": w}, x)),
                               _rmsnorm_f32(x, w), rtol=1e-5, atol=1e-6)


def test_force_ref_env(monkeypatch):
    """``REPRO_FORCE_REF=1`` routes the decode kernel to its oracle."""
    rng = np.random.default_rng(0)
    b, t, h, hkv, dh = 3, 32, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(b, 1, h, dh)), jnp.float32)
    nk, nv = (jnp.asarray(rng.normal(size=(b, 1, hkv, dh)), jnp.float32)
              for _ in range(2))
    kc, vc = (jnp.asarray(rng.normal(size=(b, t, hkv * dh)), jnp.float32)
              for _ in range(2))
    pos = jnp.asarray([0, 5, 31], jnp.int32)
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    got = ops.attention_decode_fused(q, nk, nv, kc, vc, pos)
    want = ref.ref_attention_decode(q, nk, nv, kc, vc, pos)
    for x, y in zip(got, want):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
