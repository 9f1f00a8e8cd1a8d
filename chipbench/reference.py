"""The plain reference of the benchmarked model and optimizer, and the
weights both sides start from.

Nothing here imports the program. The model is the decoder of the
configuration file, written out in ``jax.numpy`` at float32 with every
contraction at ``Precision.HIGHEST``: token embedding scaled by
sqrt(hidden_size), pre-norm layers ``h += attn(norm(h)); h += mlp(norm(h))``
with RMSNorm ``x / rms(x) * (1 + scale)``, rotary embedding on the two
halves of each head, grouped-query causal softmax attention with q/k/v
biases, a SiLU-gated MLP, a final RMSNorm and the tied head.  The
embedding scale and the ``1 + scale`` norm weights are the program's
stated parametrisation (the published Qwen2 has neither); see PERF.md.

``precision="fp8"`` is the control: every contraction's operands are
rounded to float8 e4m3 with one scale per tensor, the step below the
bfloat16 that the configurations state.

The optimizer is TVLARS (the paper's Algorithm 1, parameter-space
momentum): per stored tensor of two or more dimensions a trust ratio
``eta * |w| / (|g| + wd * |w| + eps)`` and weight decay; tensors of one
dimension take the plain base rate.  Weights live in the layout the
program stores (layers stacked on a leading axis), so a stored tensor
is one trust-ratio group, as in the program.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class Arch(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float


def arch_of(config: dict) -> Arch:
    d, h = config["hidden_size"], config["num_attention_heads"]
    return Arch(config["num_hidden_layers"], d, h,
                config["num_key_value_heads"],
                config.get("head_dim", d // h), config["intermediate_size"],
                config["vocab_size"], float(config["rope_theta"]),
                float(config["rms_norm_eps"]))


def base_key(seed: int):
    """A PRNG key from any non-negative seed, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


# --------------------------------------------------------------------------
# weights: the benchmark's own, from the seed, in the program's layout
# --------------------------------------------------------------------------

def leaf_table(a: Arch) -> list[tuple[tuple[str, ...], tuple, str]]:
    """(path, shape, kind) of every weight, in a fixed order."""
    L, d, h, kv, hd, f = (a.layers, a.d, a.heads, a.kv_heads, a.head_dim,
                          a.ffn)
    g = ("groups", "l0_attn")
    return [
        (("embed", "table"), (a.vocab, d), "embed"),
        (g + ("attn", "bk"), (L, kv, hd), "bias"),
        (g + ("attn", "bq"), (L, h, hd), "bias"),
        (g + ("attn", "bv"), (L, kv, hd), "bias"),
        (g + ("attn", "wk"), (L, d, kv, hd), "matrix"),
        (g + ("attn", "wo"), (L, h, hd, d), "matrix"),
        (g + ("attn", "wq"), (L, d, h, hd), "matrix"),
        (g + ("attn", "wv"), (L, d, kv, hd), "matrix"),
        (g + ("mlp", "wg"), (L, d, f), "matrix"),
        (g + ("mlp", "wi"), (L, d, f), "matrix"),
        (g + ("mlp", "wo"), (L, f, d), "matrix"),
        (g + ("norm1", "scale"), (L, d), "norm"),
        (g + ("norm2", "scale"), (L, d), "norm"),
        (("final_norm", "scale"), (d,), "final_norm"),
    ]


def leaf_name(path: tuple[str, ...]) -> str:
    return "/".join(path)


def _nest(pairs) -> dict:
    out: dict = {}
    for path, value in pairs:
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = value
    return out


def flat(tree: dict, a: Arch) -> list:
    """Leaves of a weight tree in ``leaf_table`` order."""
    out = []
    for path, _, _ in leaf_table(a):
        node = tree
        for k in path:
            node = node[k]
        out.append(node)
    return out


def make_weights(a: Arch, init: dict, dtype, key) -> dict:
    """Every weight from ``key`` (call under jit): normal draws at the
    scales ``init`` names, one folded key per leaf."""
    pairs = []
    for i, (path, shape, kind) in enumerate(leaf_table(a)):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
        if kind == "final_norm":
            x = init["final_norm_mean"] + init["norm_std"] * z
        else:
            x = init[f"{kind}_std"] * z
        pairs.append((path, x.astype(dtype)))
    return _nest(pairs)


def weights_fn(a: Arch, init: dict, dtype) -> Callable:
    """``seed -> weights`` as one program (jit it with the shardings the
    caller needs)."""
    def fn(key):
        return make_weights(a, init, dtype, jax.random.fold_in(key, 0))
    return fn


# --------------------------------------------------------------------------
# token batches for training: row ``r`` of step ``i``, all rows distinct
# --------------------------------------------------------------------------

def step_tokens(key, step, rows: int, seq: int, vocab: int):
    """[rows, seq + 1] token ids of one optimizer step."""
    return jax.random.randint(jax.random.fold_in(jax.random.fold_in(
        key, 1), step), (rows, seq + 1), 0, vocab, jnp.int32)


# --------------------------------------------------------------------------
# the model
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


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def _rope(x, theta):
    """x: [S, H, Dh] at positions 0..S-1; rotates the two halves."""
    s, _, dh = x.shape
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(a: Arch, mm, x, p):
    s = x.shape[0]
    up = jax.tree_util.tree_map(lambda t: t.astype(F32), p)
    at, ml = up["attn"], up["mlp"]
    y = _rms(x, up["norm1"]["scale"], a.eps)
    q = mm("sd,dhk->shk", y, at["wq"]) + at["bq"]
    k = mm("sd,dhk->shk", y, at["wk"]) + at["bk"]
    v = mm("sd,dhk->shk", y, at["wv"]) + at["bv"]
    q, k = _rope(q, a.rope_theta), _rope(k, a.rope_theta)
    rep = jnp.arange(a.heads) // (a.heads // a.kv_heads)
    k, v = k[:, rep], v[:, rep]
    scores = mm("qhd,khd->hqk", q, k) / math.sqrt(a.head_dim)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mm("hqk,khd->qhd", probs, v)
    x = x + mm("qhd,hdm->qm", o, at["wo"])
    y = _rms(x, up["norm2"]["scale"], a.eps)
    gate = jax.nn.silu(mm("sd,df->sf", y, ml["wg"]))
    x = x + mm("sf,fd->sd", gate * mm("sd,df->sf", y, ml["wi"]), ml["wo"])
    return x


def hidden(a: Arch, mm, params: dict, tokens, remat: bool = False):
    """tokens [S] -> final-normed hidden states [S, d] (float32).
    ``remat`` recomputes each layer in the backward pass (same values,
    less memory)."""
    table = params["embed"]["table"]
    x = jnp.take(table, tokens, axis=0).astype(F32) * math.sqrt(a.d)

    def body(x, p):
        return _layer(a, mm, x, p), None

    x, _ = jax.lax.scan(jax.checkpoint(body) if remat else body, x,
                        params["groups"]["l0_attn"])
    return _rms(x, params["final_norm"]["scale"], a.eps)


def logits(a: Arch, mm, params: dict, tokens):
    """tokens [S] -> logits [S, V] (float32)."""
    return mm("sd,vd->sv", hidden(a, mm, params, tokens),
              params["embed"]["table"])


CE_CHUNK = 512


def row_loss(a: Arch, mm, params: dict, row):
    """Mean next-token cross-entropy of one row of ``seq + 1`` ids,
    taken over blocks of positions so that one block of logits lives
    at a time."""
    x = hidden(a, mm, params, row[:-1], remat=True)
    s = x.shape[0]
    c = CE_CHUNK if s % CE_CHUNK == 0 else s
    table = params["embed"]["table"]

    @jax.checkpoint
    def block(xb, yb):
        lg = mm("sd,vd->sv", xb, table)
        gold = jnp.take_along_axis(lg, yb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold)

    def body(total, xs):
        return total + block(*xs), None

    total, _ = jax.lax.scan(body, jnp.zeros((), F32),
                            (x.reshape(s // c, c, -1),
                             row[1:].reshape(s // c, c)))
    return total / s


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


def tvlars(hp: Hyper, params: dict, mom: dict, grads: dict, step):
    base = base_rate(hp, step)

    def leaf(w, m, g):
        if w.ndim >= 2:
            wn, gn = jnp.linalg.norm(w), jnp.linalg.norm(g)
            ratio = jnp.where((wn > 0) & (gn > 0),
                              hp.eta * wn / (gn + hp.wd * wn + hp.eps), 1.0)
            scaled = base * ratio * (g + hp.wd * w)
        else:
            scaled = base * g
        proposed = w - scaled
        return proposed + hp.momentum * (proposed - m), proposed

    out = jax.tree_util.tree_map(leaf, params, mom, grads)
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

def train_readings(a: Arch, init: dict, hp: Hyper, key, rows_of_step,
                   seq: int, steps: int, precision: str = "f32",
                   keep_rows=None) -> dict:
    """The reference's losses, first-gradient norms and parameter-change
    norms over ``steps`` optimizer steps, one row at a time.

    ``rows_of_step(i)`` gives the ``[rows, seq + 1]`` ids of step ``i``;
    ``keep_rows(i, rows)`` optionally keeps a subset (used only to read
    planted faults)."""
    mm = contraction(precision)
    make = jax.jit(weights_fn(a, init, F32))
    grad_row = jax.value_and_grad(lambda p, r: row_loss(a, mm, p, r))

    def accumulate(acc, params, row):
        loss, g = grad_row(params, row)
        return loss, jax.tree_util.tree_map(jnp.add, acc, g)

    add = jax.jit(accumulate, donate_argnums=(0,))
    update = jax.jit(lambda p, m, g, t: tvlars(hp, p, m, g, t),
                     donate_argnums=(0, 1, 2))
    scale = jax.jit(lambda g, n: jax.tree_util.tree_map(lambda x: x / n, g),
                    donate_argnums=(0,))
    norms = jax.jit(lambda t: [jnp.linalg.norm(x) for x in flat(t, a)])
    params = make(key)
    mom = jax.tree_util.tree_map(jnp.copy, params)
    losses, grad_norms = [], None
    for i in range(steps):
        rows = rows_of_step(i)
        if keep_rows is not None:
            rows = keep_rows(i, rows)
        acc = jax.tree_util.tree_map(jnp.zeros_like, params)
        total = 0.0
        for r in range(rows.shape[0]):
            loss, acc = add(acc, params, rows[r])
            total += float(loss)
        acc = scale(acc, float(rows.shape[0]))
        losses.append(total / rows.shape[0])
        if i == 0:
            grad_norms = [float(x) for x in norms(acc)]
        params, mom = update(params, mom, acc, jnp.float32(i))
        del acc
        if i == 0:
            first = [[float(v) for v in x]
                     for x in first_update_stats(a, hp, params, make(key))]
    del mom
    p0 = make(key)
    delta = jax.jit(lambda p, q: [jnp.linalg.norm(x - y) for x, y in
                                  zip(flat(p, a), flat(q, a))])
    delta_norms = [float(x) for x in delta(params, p0)]
    return {"losses": losses, "grad_norms": grad_norms, "first": first,
            "delta_norms": delta_norms}


@jax.jit
def _stats(s, w0):
    return jnp.stack([jnp.sum(s * s), jnp.sum(s * w0), jnp.sum(w0 * w0)])


def first_update_stats(a: Arch, hp: Hyper, p1: dict, p0: dict) -> list:
    """Per tensor ``[|s|^2, s.w0, |w0|^2]`` of the first update
    ``s = (w0 - w1) / (1 + momentum)`` (Algorithm 1 with ``m0 = w0``
    moves the weights by ``(1 + momentum)`` times the scaled step)."""
    return [_stats((w0 - w1) / (1.0 + hp.momentum), w0)
            for w1, w0 in zip(flat(p1, a), flat(p0, a))]


def gap_fn(a: Arch, precision: str):
    """``(params, ids, targets) -> (gap, top)`` over one sequence:
    ``gap[j]`` is how far the reference's logit of ``targets[j]`` at
    position ``j`` lies below its best, ``top[j]`` its own first
    choice there."""
    mm = contraction(precision)

    def fn(params, ids, targets):
        lg = logits(a, mm, params, ids)
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
