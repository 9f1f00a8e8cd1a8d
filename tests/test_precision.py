"""Mixed-precision fused substrate: bf16 state + f32 master rows.

The acceptance bars for the ``precision`` policy axis:

  * ``"f32"`` is bitwise the legacy fused path;
  * ``"bf16_master[_sr]"`` matches the f32 pure-jnp reference within
    the documented precision-aware bound (``ref.parity_tolerance``),
    with the f32 delta (and therefore the f32 master params) matching
    the jnp oracle to <= 1e-6 — bf16 state buffers may disagree from
    the oracle by at most one storage ulp;
  * the whole step stays exactly TWO ``pallas_call``s at any policy;
  * state dtype/bytes actually halve: modeled per-step optimizer-state
    HBM traffic is >= 1.8x lower under bf16 (the ISSUE's criterion);
  * stochastic rounding is deterministic (counter-based hash of the
    global element index + step seed), brackets to the two neighbouring
    bf16 values, and is unbiased in expectation;
  * mixed-precision TrainStates checkpoint-round-trip bitwise.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apply_updates, build_optimizer, lamb, lars, schedules
from repro.core import flatten
from repro.core.layerwise import (PRECISIONS, _validate_precision,
                                  storage_dtype)
from repro.core.tvlars import tvlars
from repro.kernels import ops, ref
from repro.kernels.segmented_update import modeled_hbm_bytes

SHAPES = {
    "dense": {"w": (8, 16), "b": (16,)},
    "odd": (7,),
    "t3": (3, 5, 13),
    "head": (33, 65),
    "big": (130, 100),     # >1 row per segment, crosses block boundaries
}


def _problem(seed=0):
    rng = np.random.default_rng(seed)
    params = jax.tree_util.tree_map(
        lambda s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32),
        SHAPES, is_leaf=lambda x: isinstance(x, tuple))
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), p.dtype), params)
    return params, grads


def _run(opt, params, grads, steps):
    state = opt.init(params)
    p = params
    for _ in range(steps):
        u, state = opt.update(grads, state, p)
        p = apply_updates(p, u)
    return p, state


def _max_err(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


CASES = [
    ("lars", lambda uk, pr: lars(schedules.constant(0.2), use_kernel=uk,
                                 precision=pr)),
    ("lars-nesterov", lambda uk, pr: lars(schedules.constant(0.2),
                                          nesterov=True, use_kernel=uk,
                                          precision=pr)),
    ("tvlars-paper", lambda uk, pr: tvlars(0.5, lam=1e-3, delay_steps=10,
                                           momentum_style="paper",
                                           use_kernel=uk, precision=pr)),
    ("tvlars-lars", lambda uk, pr: tvlars(0.5, lam=1e-3, delay_steps=10,
                                          momentum_style="lars",
                                          use_kernel=uk, precision=pr)),
    ("lamb", lambda uk, pr: lamb(schedules.constant(0.2), use_kernel=uk,
                                 precision=pr)),
]
IDS = [c[0] for c in CASES]


# ---------------------------------------------------------------------------
# policy-vs-f32-reference: the documented tolerance model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", ["bf16_master", "bf16_master_sr"])
@pytest.mark.parametrize("name,make", CASES, ids=IDS)
def test_bf16_policy_tracks_f32_reference_within_bound(name, make,
                                                       precision):
    params, grads = _problem()
    steps = 3
    p_ref, _ = _run(make(False, "f32"), params, grads, steps)
    p_bf16, _ = _run(make("fused", precision), params, grads, steps)
    tol = ref.parity_tolerance(precision, steps)
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_bf16)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


def test_parity_tolerance_model():
    assert ref.parity_tolerance("f32") == {"rtol": 1e-6, "atol": 1e-6}
    t1 = ref.parity_tolerance("bf16_master", steps=1)
    t4 = ref.parity_tolerance("bf16_master", steps=4)
    assert t1["rtol"] == pytest.approx(4 * 2.0 ** -8)
    assert t4["rtol"] == pytest.approx(4 * t1["rtol"])


# ---------------------------------------------------------------------------
# kernel vs jnp oracle: REPRO_FORCE_REF stays ground truth at any policy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", ["bf16_master", "bf16_master_sr"])
@pytest.mark.parametrize("name,make", CASES, ids=IDS)
def test_kernel_matches_oracle_under_bf16(name, make, precision,
                                          monkeypatch):
    params, grads = _problem(seed=5)
    p_k, s_k = _run(make("fused", precision), params, grads, 2)
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    p_o, s_o = _run(make("fused", precision), params, grads, 2)
    # f32 master params: both round at the same program points
    assert _max_err(p_k, p_o) <= 1e-6
    # bf16 state: at most one storage ulp apart (an f32 last-bit
    # difference between pallas-interpret and jnp can cross a bf16
    # rounding boundary)
    for a, b in zip(jax.tree_util.tree_leaves(s_k)[1:],
                    jax.tree_util.tree_leaves(s_o)[1:]):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2.0 ** -7, atol=2.0 ** -7)


# ---------------------------------------------------------------------------
# structural invariants: dtype, launch count, f32 bitwise-legacy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("name,make", [CASES[0], CASES[4]],
                         ids=["lars", "lamb"])
def test_state_dtype_and_delta_dtype(name, make, precision):
    params, grads = _problem()
    opt = make("fused", precision)
    state = opt.init(params)
    want = storage_dtype(precision)
    for buf in jax.tree_util.tree_leaves(state)[1:]:
        assert buf.dtype == want
        assert buf.shape[1] == flatten.LANES
    updates, state2 = opt.update(grads, state, params)
    for u in jax.tree_util.tree_leaves(updates):
        assert u.dtype == jnp.float32      # delta is ALWAYS f32
    for buf in jax.tree_util.tree_leaves(state2)[1:]:
        assert buf.dtype == want


_kernels_dispatched = pytest.mark.skipif(
    os.environ.get("REPRO_FORCE_REF", "0") == "1",
    reason="REPRO_FORCE_REF=1 routes to the jnp oracle: 0 pallas_calls "
           "by design")


@_kernels_dispatched
@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("name,make", CASES, ids=IDS)
def test_exactly_two_pallas_calls_at_any_policy(name, make, precision):
    params, grads = _problem()
    opt = make("fused", precision)
    state = opt.init(params)
    jx = jax.make_jaxpr(lambda g, s, p: opt.update(g, s, p))(
        grads, state, params)
    assert ops.count_pallas_calls(jx.jaxpr) == 2


def test_f32_policy_is_bitwise_default():
    params, grads = _problem(seed=9)
    p_default, s_default = _run(
        lars(schedules.constant(0.2), use_kernel="fused"),
        params, grads, 2)
    p_f32, s_f32 = _run(
        lars(schedules.constant(0.2), use_kernel="fused",
             precision="f32"), params, grads, 2)
    assert _max_err(p_default, p_f32) == 0.0
    assert _max_err(s_default, s_f32) == 0.0


def test_validate_precision_raises():
    with pytest.raises(ValueError, match="fused"):
        lars(schedules.constant(0.1), use_kernel=False,
             precision="bf16_master")
    with pytest.raises(ValueError, match="precision"):
        lars(schedules.constant(0.1), use_kernel="fused",
             precision="fp8")
    with pytest.raises(ValueError, match="sgd"):
        build_optimizer("sgd", total_steps=10, precision="bf16_master")
    with pytest.raises(ValueError, match="fused"):
        build_optimizer("lamb", total_steps=10, precision="bf16_master")
    _validate_precision("bf16_master", "fused", "ok")   # no raise


def test_build_optimizer_precision_plumbs_through():
    params, grads = _problem(seed=11)
    for name in ("wa-lars", "nowa-lars", "lambc-lars", "lamb", "tvlars"):
        opt = build_optimizer(name, total_steps=10, learning_rate=0.2,
                              use_kernel="fused", precision="bf16_master")
        state = opt.init(params)
        for buf in jax.tree_util.tree_leaves(state)[1:]:
            assert buf.dtype == jnp.bfloat16
        u, _ = opt.update(grads, state, params)
        assert all(x.dtype == jnp.float32
                   for x in jax.tree_util.tree_leaves(u))


# ---------------------------------------------------------------------------
# dtype-aware tiling
# ---------------------------------------------------------------------------

def test_max_block_rows_per_dtype():
    assert flatten.max_block_rows(jnp.float32) == flatten.MAX_BLOCK_ROWS
    assert flatten.max_block_rows(jnp.float32) == 512
    assert flatten.max_block_rows(jnp.bfloat16) == 1024
    # invariant the tile budget encodes: equal BYTES per tile
    for dt in (jnp.float32, jnp.bfloat16):
        rows = flatten.max_block_rows(dt)
        assert rows * flatten.LANES * jnp.dtype(dt).itemsize \
            == flatten.BLOCK_BYTES


def test_bf16_spec_block_sizing():
    big = {"w": jnp.ones((2048, 128))}     # 2048 rows = both budgets
    for dt, want in ((jnp.float32, 512), (jnp.bfloat16, 1024)):
        spec = flatten.build_spec(big, dtype=dt)
        assert spec.block_rows == want
        assert spec.num_rows % want == 0
    # small trees round rows up to the dtype's min sublane tile
    small = {"w": jnp.ones((9, 16))}       # 2 rows raw
    assert flatten.build_spec(small, dtype=jnp.float32).num_rows == 8
    assert flatten.build_spec(small, dtype=jnp.bfloat16).num_rows == 16


def test_spec_cache_is_dtype_keyed():
    params, _ = _problem()
    s32 = flatten.build_spec(params, dtype=jnp.float32)
    sbf = flatten.build_spec(params, dtype=jnp.bfloat16)
    assert s32 is not sbf
    assert s32.dtype == jnp.dtype(jnp.float32)
    assert sbf.dtype == jnp.dtype(jnp.bfloat16)
    assert flatten.build_spec(params, dtype=jnp.bfloat16) is sbf


# ---------------------------------------------------------------------------
# stochastic rounding: deterministic, bracketing, unbiased
# ---------------------------------------------------------------------------

def test_sr_deterministic_and_seed_dependent():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(64, 128)) * 0.1, jnp.float32)
    idx = ref.element_index(64, 128)
    a = ref.store(x, jnp.bfloat16, bits=ref.buf_bits(idx, 0, 0))
    b = ref.store(x, jnp.bfloat16, bits=ref.buf_bits(idx, 0, 0))
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
    c = ref.store(x, jnp.bfloat16, bits=ref.buf_bits(idx, 1, 0))
    assert (np.asarray(a, np.float32) != np.asarray(c, np.float32)).any()


def test_sr_brackets_to_neighbouring_bf16():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(32, 128)), jnp.float32)
    idx = ref.element_index(32, 128)
    sr = np.asarray(ref.store(x, jnp.bfloat16,
                              bits=ref.buf_bits(idx, 7, 0)), np.float32)
    # bits=0 truncates (round toward zero); bits=0xFFFF always bumps a
    # non-exact value to the next representable away from zero
    zeros = jnp.zeros((32, 128), jnp.uint32)
    lo = np.asarray(ref.store(x, jnp.bfloat16, bits=zeros), np.float32)
    hi = np.asarray(ref.store(x, jnp.bfloat16,
                              bits=zeros + 0xFFFF), np.float32)
    assert ((sr == lo) | (sr == hi)).all()


def test_sr_is_unbiased_in_expectation():
    # x sits 30% of the way between two bf16 neighbours: P(round up)
    # should be ~0.30 over many independent hash streams
    lo = np.float32(np.asarray(jnp.asarray(1.0, jnp.bfloat16)))
    ulp = np.float32(2.0 ** -7)    # bf16 ulp at 1.0 (7 stored bits)
    frac = 0.3
    x = jnp.full((256, 128), lo + frac * ulp, jnp.float32)
    idx = ref.element_index(256, 128)
    out = np.asarray(ref.store(x, jnp.bfloat16,
                               bits=ref.buf_bits(idx, 42, 0)), np.float32)
    p_up = float((out > lo).mean())
    assert abs(p_up - frac) < 0.02
    # round-to-nearest would give 0% up here — SR is genuinely active
    rn = np.asarray(ref.store(x, jnp.bfloat16), np.float32)
    assert (rn == lo).all()


def test_sr_preserves_exact_values_and_nonfinite():
    # exactly-representable values never move, any bits
    x = jnp.asarray([[1.0, -2.5, 0.0, 0.015625] * 32], jnp.float32)
    bits = ref.buf_bits(ref.element_index(1, 128), 9, 0)
    out = np.asarray(ref.store(x, jnp.bfloat16, bits=bits), np.float32)
    np.testing.assert_array_equal(out, np.asarray(x))
    y = jnp.asarray([[np.inf, -np.inf, np.nan, 1.0] * 32], jnp.float32)
    out = np.asarray(ref.store(y, jnp.bfloat16, bits=bits), np.float32)
    assert np.isposinf(out[0, 0]) and np.isneginf(out[0, 1])
    assert np.isnan(out[0, 2])


def test_sr_policy_momentum_differs_from_rn_policy():
    params, grads = _problem(seed=13)
    _, s_rn = _run(lars(schedules.constant(0.2), use_kernel="fused",
                        precision="bf16_master"), params, grads, 3)
    _, s_sr = _run(lars(schedules.constant(0.2), use_kernel="fused",
                        precision="bf16_master_sr"), params, grads, 3)
    assert _max_err(s_rn[1:], s_sr[1:]) > 0.0


# ---------------------------------------------------------------------------
# the ISSUE's acceptance criterion: >= 1.8x lower state bytes/step
# ---------------------------------------------------------------------------

@_kernels_dispatched
def test_state_traffic_ratio_meets_acceptance():
    from repro.training.train_state import TrainState, opt_buffer_bytes
    # 2046 + 1 + 1 = 2048 rows: a whole number of tiles under BOTH
    # budgets, so the ratio isolates the dtype (padding-free; trees
    # that pad a partial tile shift it either way — the bench reports
    # the registry trees' actual numbers)
    params = {"big": jnp.ones((1023, 256)), "b": jnp.ones((9,)),
              "c": jnp.ones((128,))}
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    per_policy = {}
    for prec in ("f32", "bf16_master"):
        opt = lamb(schedules.constant(0.2), use_kernel="fused",
                   precision=prec)
        state = TrainState.create(params, opt)
        jx = jax.make_jaxpr(lambda g, s, p: opt.update(g, s, p))(
            grads, state.opt_state, params)
        rows = jax.tree_util.tree_leaves(state.opt_state)[1].shape[0]
        hbm = modeled_hbm_bytes(
            "lamb", rows,
            itemsize=jnp.dtype(storage_dtype(prec)).itemsize)
        per_policy[prec] = (hbm, opt_buffer_bytes(state),
                            ops.count_pallas_calls(jx.jaxpr))
    f32, bf16 = per_policy["f32"], per_policy["bf16_master"]
    assert f32[2] == bf16[2] == 2              # unchanged launch count
    assert f32[0]["state"] / bf16[0]["state"] >= 1.8
    assert f32[1] / bf16[1] >= 1.8             # resident bytes too


def test_modeled_hbm_bytes_shape():
    lars_t = modeled_hbm_bytes("lars", 512, itemsize=4)
    lamb_t = modeled_hbm_bytes("lamb", 512, itemsize=4)
    n = 512 * flatten.LANES
    assert lars_t["state"] == 2 * n * 4        # 1 buf: read + write
    assert lamb_t["state"] == 6 * n * 4        # 2 bufs x (2 reads + write)
    assert lars_t["delta"] == lamb_t["delta"] == 4 * n   # always f32
    assert lars_t["total"] == sum(v for k, v in lars_t.items()
                                  if k != "total")
    with pytest.raises(ValueError):
        modeled_hbm_bytes("adamw", 512, itemsize=4)


# ---------------------------------------------------------------------------
# checkpoint round-trip of mixed-precision state (single device; the
# cross-mesh variant lives in test_mesh_train.py's multidevice lane)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", ["bf16_master", "bf16_master_sr"])
def test_checkpoint_roundtrip_mixed_precision(tmp_path, precision):
    from repro.checkpoint.checkpoint import restore, save
    from repro.training.train_state import TrainState
    params, grads = _problem(seed=17)
    opt = tvlars(0.5, lam=1e-3, delay_steps=10, use_kernel="fused",
                 precision=precision)
    state = TrainState.create(params, opt)
    u, os_ = opt.update(grads, state.opt_state, state.params)
    state = TrainState(state.step + 1, apply_updates(state.params, u), os_)

    path = str(tmp_path / "ckpt")
    save(path, state, step=1)
    got = restore(path, state)
    # bitwise: f32 master params AND bf16 substrate buffers
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(got)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # the next step is bit-identical to the uninterrupted run
    u1, s1 = opt.update(grads, state.opt_state, state.params)
    u2, s2 = opt.update(grads, got.opt_state, got.params)
    assert _max_err(u1, u2) == 0.0
    assert _max_err(s1, s2) == 0.0
