"""The plain reference of the benchmarked models and optimizer, and the
weights both sides start from.

Nothing here imports the program. Each model is written out in
``jax.numpy`` at float32 with every contraction at
``Precision.HIGHEST``, by the module of its architecture
(``chipbench/arch/<model_type>.py``, reached through ``arch.load``),
which also makes the weights and names each stored tensor's role.

``precision="fp8"`` is the control: every contraction's operands are
rounded to float8 e4m3 with one scale per tensor, the step below the
bfloat16 that the configurations state.

The optimizer is TVLARS (the paper's Algorithm 1, parameter-space
momentum): per stored tensor of the role ``adapt`` a trust ratio
``eta * |w| / (|g| + wd * |w| + eps)`` and weight decay; a ``plain``
tensor takes the plain base rate; a ``held`` tensor is left to the
architecture's ``after_step``. Weights live in the layout the program
stores (layers stacked on a leading axis), so a stored tensor is one
trust-ratio group, as in the program.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from chipbench import arch

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def base_key(seed: int):
    """A PRNG key from any non-negative seed, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


# --------------------------------------------------------------------------
# weights: the benchmark's own, from the seed, in the program's layout
# --------------------------------------------------------------------------

def leaf_name(path: tuple[str, ...]) -> str:
    return "/".join(path)


def nest(pairs) -> dict:
    """A tree from ``(path, value)`` pairs."""
    out: dict = {}
    for path, value in pairs:
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = value
    return out


def flat(tree: dict, config: dict) -> list:
    """Leaves of a weight tree in the architecture's ``leaves`` order."""
    out = []
    for path, _, _ in arch.load(config).leaves(config):
        node = tree
        for k in path:
            node = node[k]
        out.append(node)
    return out


def role_tree(config: dict) -> dict:
    """The weight tree's shape with each leaf's role in its place."""
    return nest((path, role) for path, _, role in
                 arch.load(config).leaves(config))


def weights_fn(config: dict, dtype) -> Callable:
    """``seed -> weights`` as one program (jit it with the shardings the
    caller needs)."""
    make = arch.load(config).init_weights(config, dtype)

    def fn(key):
        return make(jax.random.fold_in(key, 0))
    return fn


# --------------------------------------------------------------------------
# token batches for training: row ``r`` of step ``i``, all rows distinct
# --------------------------------------------------------------------------

def step_tokens(key, step, rows: int, seq: int, vocab: int):
    """[rows, seq + 1] token ids of one optimizer step."""
    return jax.random.randint(jax.random.fold_in(jax.random.fold_in(
        key, 1), step), (rows, seq + 1), 0, vocab, jnp.int32)


# --------------------------------------------------------------------------
# contractions
# --------------------------------------------------------------------------

def _q8(x):
    """Round to float8 e4m3 with one scale for the tensor."""
    x = x.astype(F32)
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def contraction(precision: str) -> Callable:
    if precision == "f32":
        return lambda eq, x, y: jnp.einsum(
            eq, x.astype(F32), y.astype(F32), precision=HIGHEST,
            preferred_element_type=F32)
    if precision == "fp8":
        return lambda eq, x, y: jnp.einsum(
            eq, _q8(x), _q8(y), precision=HIGHEST,
            preferred_element_type=F32)
    raise ValueError(f"unknown precision {precision!r}")


# --------------------------------------------------------------------------
# TVLARS (Algorithm 1, parameter-space momentum)
# --------------------------------------------------------------------------

class Hyper(NamedTuple):
    lr: float            # gamma_target
    lam: float
    delay: float
    alpha: float
    gamma_min: float
    eta: float
    momentum: float
    wd: float
    eps: float


def base_rate(hp: Hyper, step) -> jnp.ndarray:
    psi = jnp.clip(hp.lam * (jnp.asarray(step, F32) - hp.delay), -60., 60.)
    return hp.lr * (1.0 / (hp.alpha + jnp.exp(psi)) + hp.gamma_min)


def tvlars(hp: Hyper, params: dict, mom: dict, grads: dict, step,
           roles: dict):
    """One step over the weight tree; ``roles`` is ``role_tree``."""
    base = base_rate(hp, step)

    def leaf(w, m, g, role):
        if role == "held":
            return w, m
        if role == "adapt":
            wn, gn = jnp.linalg.norm(w), jnp.linalg.norm(g)
            ratio = jnp.where((wn > 0) & (gn > 0),
                              hp.eta * wn / (gn + hp.wd * wn + hp.eps), 1.0)
            scaled = base * ratio * (g + hp.wd * w)
        elif role == "plain":
            scaled = base * g
        else:
            raise ValueError(f"unknown leaf role {role!r}")
        proposed = w - scaled
        return proposed + hp.momentum * (proposed - m), proposed

    out = jax.tree_util.tree_map(leaf, params, mom, grads, roles)
    is_pair = lambda t: isinstance(t, tuple)  # noqa: E731
    return (jax.tree_util.tree_map(lambda t: t[0], out, is_leaf=is_pair),
            jax.tree_util.tree_map(lambda t: t[1], out, is_leaf=is_pair))


def first_grad_norm(hp: Hyper, adapt: bool, u2: float, uw: float,
                    w2: float) -> float:
    """|g| of the first step from the first update ``s = w0 - m1``
    (``u2 = |s|^2``, ``uw = s.w0``, ``w2 = |w0|^2``).

    With ``B = base * eta`` and ``c = gamma * wd`` the update is
    ``s = gamma (g + wd w0)`` with ``gamma = B|w0| / (|g| + wd|w0|)``;
    eliminating ``g`` gives ``c = (w2 B^2 - u2) / (2 (w2 B - uw))``.
    A plain tensor's update is ``base * g``."""
    base = float(base_rate(hp, 0))
    if not adapt:
        return math.sqrt(u2) / base
    b = base * hp.eta
    den = 2.0 * (w2 * b - uw)
    if den == 0.0:
        return math.nan
    c = (w2 * b * b - u2) / den
    gamma = c / hp.wd
    if gamma <= 0.0:
        return math.nan
    return math.sqrt(w2) * (b - c) / gamma


# --------------------------------------------------------------------------
# readings the check compares
# --------------------------------------------------------------------------

def train_readings(config: dict, hp: Hyper, key, rows_of_step, seq: int,
                   steps: int, precision: str = "f32",
                   keep_rows=None) -> dict:
    """The reference's losses, first-gradient norms and parameter-change
    norms over ``steps`` optimizer steps, one row at a time.

    ``rows_of_step(i)`` gives the ``[rows, seq + 1]`` ids of step ``i``;
    ``keep_rows(i, rows)`` optionally keeps a subset (used only to read
    planted faults). Each step is TVLARS over the ``adapt`` and
    ``plain`` leaves, then the architecture's ``after_step`` with the
    ``aux`` of the step's rows summed."""
    model = arch.load(config)
    mm = contraction(precision)
    roles = role_tree(config)
    make = jax.jit(weights_fn(config, F32))
    grad_row = jax.value_and_grad(
        lambda p, r: model.row_loss(config, mm, p, r), has_aux=True)

    def accumulate(acc, params, row):
        (loss, aux), g = grad_row(params, row)
        return loss, aux, jax.tree_util.tree_map(jnp.add, acc, g)

    def step_fn(p, m, g, aux, t):
        p, m = tvlars(hp, p, m, g, t, roles)
        return model.after_step(config, p, aux), m

    add = jax.jit(accumulate, donate_argnums=(0,))
    update = jax.jit(step_fn, donate_argnums=(0, 1, 2))
    scale = jax.jit(lambda g, n: jax.tree_util.tree_map(lambda x: x / n, g),
                    donate_argnums=(0,))
    norms = jax.jit(lambda t: [jnp.linalg.norm(x) for x in flat(t, config)])
    params = make(key)
    mom = jax.tree_util.tree_map(jnp.copy, params)
    losses, grad_norms = [], None
    for i in range(steps):
        rows = rows_of_step(i)
        if keep_rows is not None:
            rows = keep_rows(i, rows)
        acc = jax.tree_util.tree_map(jnp.zeros_like, params)
        total, aux_sum = 0.0, None
        for r in range(rows.shape[0]):
            loss, aux, acc = add(acc, params, rows[r])
            total += float(loss)
            aux_sum = aux if aux_sum is None else jax.tree_util.tree_map(
                jnp.add, aux_sum, aux)
        acc = scale(acc, float(rows.shape[0]))
        losses.append(total / rows.shape[0])
        if i == 0:
            grad_norms = [float(x) for x in norms(acc)]
        params, mom = update(params, mom, acc, aux_sum, jnp.float32(i))
        del acc
        if i == 0:
            first = [[float(v) for v in x] for x in
                     first_update_stats(config, hp, params, make(key))]
    del mom
    p0 = make(key)
    delta = jax.jit(lambda p, q: [jnp.linalg.norm(x - y) for x, y in
                                  zip(flat(p, config), flat(q, config))])
    delta_norms = [float(x) for x in delta(params, p0)]
    return {"losses": losses, "grad_norms": grad_norms, "first": first,
            "delta_norms": delta_norms}


@jax.jit
def _stats(s, w0):
    return jnp.stack([jnp.sum(s * s), jnp.sum(s * w0), jnp.sum(w0 * w0)])


def first_update_stats(config: dict, hp: Hyper, p1: dict,
                       p0: dict) -> list:
    """Per tensor ``[|s|^2, s.w0, |w0|^2]`` of the first update
    ``s = (w0 - w1) / (1 + momentum)`` (Algorithm 1 with ``m0 = w0``
    moves the weights by ``(1 + momentum)`` times the scaled step)."""
    return [_stats((w0 - w1) / (1.0 + hp.momentum), w0)
            for w1, w0 in zip(flat(p1, config), flat(p0, config))]


def gap_fn(config: dict, precision: str):
    """``(params, ids, targets) -> (gap, top)`` over one sequence:
    ``gap[j]`` is how far the reference's logit of ``targets[j]`` at
    position ``j`` lies below its best, ``top[j]`` its own first
    choice there."""
    mm = contraction(precision)
    model = arch.load(config)

    def fn(params, ids, targets):
        lg = model.logits(config, mm, params, ids)
        got = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - got, \
            jnp.argmax(lg, axis=-1).astype(jnp.int32)

    return jax.jit(fn)


def worst_gap(ref_norms, prog_norms, keep) -> tuple[float, int]:
    """Largest ``|prog - ref|`` over the kept leaves, each measured
    against the larger of its own reference norm and the median kept
    leaf's; returns (gap, leaf index)."""
    kept = [r for r, k in zip(ref_norms, keep) if k]
    med = sorted(kept)[len(kept) // 2]
    worst, at = 0.0, -1
    for i, (r, p, k) in enumerate(zip(ref_norms, prog_norms, keep)):
        if not k:
            continue
        g = abs(p - r) / max(r, med)
        if not math.isfinite(g):
            return math.inf, i
        if g > worst:
            worst, at = g, i
    return worst, at
