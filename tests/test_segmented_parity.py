"""Segmented-substrate parity: fused vs pure-jnp tree path vs oracle.

The acceptance bar for the fused multi-tensor path: per-step updates
match the per-leaf reference math to <=1e-6 over mixed-shape trees
(1-D bypass leaves, odd sizes, bf16 params), for LARS (nesterov,
trust_clip), TVLARS (both momentum styles) and LAMB — and the whole
step issues exactly TWO pallas_calls regardless of leaf count.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apply_updates, build_optimizer, lamb, lars, schedules
from repro.core.layerwise import normalize_use_kernel
from repro.core.tvlars import tvlars
from repro.kernels import ops

MIXED_SHAPES = {
    "dense": {"w": (8, 16), "b": (16,)},   # classic matrix + 1-D bypass
    "odd": (7,),                            # odd 1-D
    "t3": (3, 5, 13),                       # odd 3-D
    "head": (33, 65),                       # crosses a lane row
}


def _problem(seed=0, bf16_leaf=True):
    rng = np.random.default_rng(seed)
    def leaf(s, dt):
        return jnp.asarray(rng.normal(size=s) * 0.3, dt)
    params = jax.tree_util.tree_map(
        lambda s: leaf(s, jnp.float32), MIXED_SHAPES,
        is_leaf=lambda x: isinstance(x, tuple))
    if bf16_leaf:
        params["head"] = params["head"].astype(jnp.bfloat16)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), p.dtype), params)
    return params, grads


def _run(opt, params, grads, steps):
    state = opt.init(params)
    p = params
    for _ in range(steps):
        u, state = opt.update(grads, state, p)
        p = apply_updates(p, u)
    return p


def _assert_trees_close(a, b, rtol, atol):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


OPTIMIZER_CASES = [
    ("lars", lambda uk: lars(schedules.constant(0.2), use_kernel=uk)),
    ("lars-nesterov", lambda uk: lars(schedules.constant(0.2),
                                      nesterov=True, use_kernel=uk)),
    ("lars-clip", lambda uk: lars(schedules.constant(0.2),
                                  trust_clip=5e-4, use_kernel=uk)),
    ("tvlars-paper", lambda uk: tvlars(0.5, lam=1e-3, delay_steps=10,
                                       momentum_style="paper",
                                       use_kernel=uk)),
    ("tvlars-lars", lambda uk: tvlars(0.5, lam=1e-3, delay_steps=10,
                                      momentum_style="lars",
                                      use_kernel=uk)),
    ("lamb", lambda uk: lamb(schedules.constant(0.2), use_kernel=uk)),
]


@pytest.mark.parametrize("name,make", OPTIMIZER_CASES,
                         ids=[c[0] for c in OPTIMIZER_CASES])
def test_fused_single_step_matches_reference_1e6(name, make):
    """The segmented UPDATE (f32 deltas) == the pure-jnp one to <=1e-6.

    Deltas, not stored params: a bf16 leaf can flip one storage ulp
    when an ~1e-8 norm-accumulation-order difference lands on a
    rounding boundary."""
    params, grads = _problem()
    o_ref, o_fused = make(False), make("fused")
    u_ref, _ = o_ref.update(grads, o_ref.init(params), params)
    u_fused, _ = o_fused.update(grads, o_fused.init(params), params)
    _assert_trees_close(u_ref, u_fused, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("name,make", OPTIMIZER_CASES,
                         ids=[c[0] for c in OPTIMIZER_CASES])
def test_fused_multi_step_matches_reference(name, make):
    params, grads = _problem(seed=3)
    _assert_trees_close(_run(make(False), params, grads, 4),
                        _run(make("fused"), params, grads, 4),
                        rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("name,make", OPTIMIZER_CASES,
                         ids=[c[0] for c in OPTIMIZER_CASES])
def test_fused_matches_ref_oracle(name, make, monkeypatch):
    """Segmented Pallas kernels vs the pure-jnp segmented oracle."""
    params, grads = _problem(seed=5)
    kernel = _run(make("fused"), params, grads, 2)
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    oracle = _run(make("fused"), params, grads, 2)
    _assert_trees_close(kernel, oracle, rtol=1e-6, atol=1e-6)


def test_fused_matches_tree_path_nesterov_trust_clip():
    params, grads = _problem(seed=7)
    def make(uk):
        return lars(schedules.constant(0.3), nesterov=True, trust_clip=5e-4,
                    use_kernel=uk)
    _assert_trees_close(_run(make(False), params, grads, 3),
                        _run(make("fused"), params, grads, 3),
                        rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("name,make", [OPTIMIZER_CASES[0],
                                       OPTIMIZER_CASES[5]],
                         ids=["lars", "lamb"])
def test_fused_multi_block_grid_accumulation(name, make):
    """MIXED_SHAPES packs into one kernel block (grid=1); this tree
    packs >512 rows so the cross-grid-iteration norm accumulation
    (pl.when init + revisited table block) actually executes."""
    rng = np.random.default_rng(13)
    params = {"big": jnp.asarray(rng.normal(size=(1024, 256)) * 0.1,
                                 jnp.float32),
              "b": jnp.asarray(rng.normal(size=(9,)), jnp.float32)}
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), p.dtype), params)
    from repro.core.flatten import MAX_BLOCK_ROWS, build_spec
    spec = build_spec(params)
    assert spec.num_rows > MAX_BLOCK_ROWS   # multi-block, not grid=(1,)
    _assert_trees_close(_run(make(False), params, grads, 2),
                        _run(make("fused"), params, grads, 2),
                        rtol=2e-5, atol=1e-6)


def test_use_kernel_true_aliases_fused():
    assert normalize_use_kernel(True) == "fused"
    assert normalize_use_kernel(None) is False
    with pytest.raises(ValueError):
        normalize_use_kernel("warp")


def test_unknown_use_kernel_raises_naming_accepted_values():
    accepted = r"expected one of \(False, None, True, 'fused'\)"
    with pytest.raises(ValueError, match=accepted):
        lars(schedules.constant(0.1), use_kernel="per_tensor")
    with pytest.raises(ValueError, match=accepted):
        tvlars(0.5, use_kernel="per_tensor", momentum_style="lars")
    with pytest.raises(ValueError, match=accepted):
        lamb(schedules.constant(0.1), use_kernel="per_tensor")
    with pytest.raises(ValueError, match=accepted):
        build_optimizer("wa-lars", total_steps=10, use_kernel="per_tensor")
    with pytest.raises(ValueError, match="sgd"):
        build_optimizer("sgd", total_steps=10, use_kernel="fused")


# ---------------------------------------------------------------------------
# kernel-launch accounting: the point of the substrate
# ---------------------------------------------------------------------------

_kernels_dispatched = pytest.mark.skipif(
    os.environ.get("REPRO_FORCE_REF", "0") == "1",
    reason="REPRO_FORCE_REF=1 routes to the jnp oracle: 0 pallas_calls "
           "by design")


@_kernels_dispatched
@pytest.mark.parametrize("name,make", OPTIMIZER_CASES,
                         ids=[c[0] for c in OPTIMIZER_CASES])
def test_fused_issues_exactly_two_pallas_calls(name, make):
    params, grads = _problem()
    opt = make("fused")
    state = opt.init(params)
    jx = jax.make_jaxpr(lambda g, s, p: opt.update(g, s, p))(
        grads, state, params)
    assert ops.count_pallas_calls(jx.jaxpr) == 2


def test_tree_path_issues_no_pallas_calls():
    """What GSPMD relies on: a Pallas call cannot be partitioned."""
    params, grads = _problem()
    opt = lars(schedules.constant(0.2), use_kernel=False)
    state = opt.init(params)
    jx = jax.make_jaxpr(lambda g, s, p: opt.update(g, s, p))(
        grads, state, params)
    assert ops.count_pallas_calls(jx.jaxpr) == 0


def test_build_optimizer_fused_smoke():
    """Factory-level wiring: every family accepts use_kernel='fused'."""
    params, grads = _problem(seed=11)
    for name in ("wa-lars", "nowa-lars", "lambc-lars", "lamb", "tvlars"):
        opt_r = build_optimizer(name, total_steps=10, learning_rate=0.2)
        opt_f = build_optimizer(name, total_steps=10, learning_rate=0.2,
                                use_kernel="fused")
        _assert_trees_close(_run(opt_r, params, grads, 2),
                            _run(opt_f, params, grads, 2),
                            rtol=2e-5, atol=1e-6)
