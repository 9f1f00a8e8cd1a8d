"""Losses: cross-entropy (CLF/LM) and Barlow Twins (SSL, Zbontar 2021).

The problem statement Eq. (1) is CE + (λ/2)‖w‖²; weight decay is applied
inside the optimizers (Eq. 2's wd term), so losses here are pure data
terms.

Every loss here is **mean-reduced** over the batch. The accumulation
engine relies on that: :class:`WeightedMean` folds K per-microbatch
means (each weighted by its sample count) into the global-batch mean,
so K microbatches of B/K samples reproduce the 1×B statistics exactly
for mean-reduced quantities.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.obs import scopes


class WeightedMean(NamedTuple):
    """Running weighted mean ``total/weight`` in f32 (scan-carry safe).

    ``total`` = Σ wᵢ·vᵢ, ``weight`` = Σ wᵢ. For K equal-weight
    microbatch means this finalizes to the plain mean of means ≡ the
    global-batch mean; unequal microbatches stay correct because each
    contributes proportionally to its sample count.
    """
    total: jnp.ndarray
    weight: jnp.ndarray

    @classmethod
    def zero(cls) -> "WeightedMean":
        return cls(jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))

    def add(self, value, weight=1.0) -> "WeightedMean":
        w = jnp.asarray(weight, jnp.float32)
        return WeightedMean(self.total + w * jnp.asarray(value, jnp.float32),
                            self.weight + w)

    def result(self) -> jnp.ndarray:
        return self.total / jnp.maximum(self.weight, 1e-12)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """logits [..., C], labels [...] int -> scalar mean CE (f32).

    The gold logit is selected with an iota==label mask rather than
    ``take_along_axis``: a gather along a sharded vocab dim makes GSPMD
    replicate the (huge) logits over the data axes, while the masked
    reduction stays sharded exactly like the logits (measured 13×
    memory difference on train_4k).
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = labels[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    return jnp.mean(logz - gold)


def accuracy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels
                     ).astype(jnp.float32))


CE_CHUNK = 256
LANES = 128


def fused_ce_from_hidden(h: jnp.ndarray, unembed_w: jnp.ndarray,
                         labels: jnp.ndarray,
                         vocab_shards: int = 1) -> jnp.ndarray:
    """Chunked softmax cross-entropy fused with the unembed projection.

    Materialising [B, S, V] logits (plus their f32 CE copies and the f32
    head gradient) dominated train-step memory (~12 GiB/dev on
    qwen2-72b), so the logits are made one block at a time. The loop
    runs over whichever axis keeps its gradient carry the smaller:

    * fewer tokens than vocabulary entries (T = B·S < V): vocabulary
      chunks (:func:`_vocab_chunked_ce`). The backward carries the
      [T, D] hidden-state gradient and writes each chunk's rows of the
      head gradient once.
    * otherwise: sequence chunks, a scan with a checkpointed body that
      keeps one [B, CE_CHUNK, V] logits block live; the backward
      recomputes each block and adds its head gradient into a [D, V]
      carry.

    h: [B, S, D]; unembed_w: [D, V]; labels: [B, S] -> scalar mean CE.
    ``vocab_shards``: the shards a mesh splits the vocabulary into (1
    off a mesh); each vocabulary chunk then takes the same columns of
    every shard, so no chunk crosses a shard boundary.
    """
    b, s, d = h.shape
    rows = unembed_w.T                  # the table's [V, D] layout
    if b * s < rows.shape[0]:
        return _vocab_chunked_ce(h.reshape(b * s, d), rows,
                                 labels.reshape(b * s), vocab_shards)
    chunk = CE_CHUNK if s % CE_CHUNK == 0 else s
    nblk = s // chunk
    hc = jnp.moveaxis(h.reshape(b, nblk, chunk, d), 1, 0)
    yc = jnp.moveaxis(labels.reshape(b, nblk, chunk), 1, 0)
    ids = jnp.arange(rows.shape[0])

    @jax.checkpoint
    def chunk_ce(h_blk, y_blk):
        logits = _logits(h_blk, rows)
        gold = jnp.sum(jnp.where(_onehot(y_blk, ids), logits, 0.0),
                       axis=-1)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    def body(acc, xs):
        h_blk, y_blk = xs
        return acc + chunk_ce(h_blk, y_blk), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, yc))
    return total / (b * s)


def _logits(h, rows):
    """f32 logits of hidden states h [..., D] against table rows
    [..., D]: matmul inputs in the compute dtype (the MXU accumulates in
    f32), the product rounded to that dtype, as autodiff of ``h @ w``
    rounds it."""
    dims = (((h.ndim - 1,), (rows.ndim - 1,)), ((), ()))
    return jax.lax.dot_general(h, rows, dims).astype(jnp.float32)


def _onehot(labels, ids):
    """Mask [*labels.shape, *ids.shape]: where each label equals a
    vocabulary id of the block."""
    return labels.reshape(labels.shape + (1,) * ids.ndim) == ids


def vocab_split(tokens: int, vocab: int) -> tuple[int, int, int]:
    """(width, full, tail): the vocabulary loop over ``vocab`` columns
    runs ``full`` chunks of ``width`` columns, then one of ``tail``
    (when not 0).

    About ``ceil(tokens / CE_CHUNK)`` chunks, so a [tokens, width] f32
    logits block holds about as many entries as the sequence loop's
    [CE_CHUNK, V] one. Widths are whole lanes; the tail takes what is
    left (a prime multiple of 128, such as 151,936 = 128·1187, has no
    equal aligned split)."""
    n = -(-tokens // CE_CHUNK)
    width = min(vocab, LANES * -(-vocab // (n * LANES)))
    return width, vocab // width, vocab % width


def _vocab_loop(step, carry, tokens, per_shard, xs, x_tail):
    """``step(carry, start, width, x) -> (carry, y)`` over the chunks of
    ``per_shard`` columns: one scan over the equal chunks (``xs`` and
    the stacked ``ys`` lead with the chunk), then the tail's step, so
    the program's size does not grow with the chunk count."""
    width, full, tail = vocab_split(tokens, per_shard)
    carry, ys = jax.lax.scan(
        lambda c, x: step(c, x[0] * width, width, x[1]), carry,
        (jnp.arange(full), xs))
    y_tail = None
    if tail:
        carry, y_tail = step(carry, full * width, tail, x_tail)
    return carry, ys, y_tail


def _chunk_ids(rows, start, width):
    """Vocabulary ids [S, width] of columns [start, start + width) of
    each of the S shards of ``rows`` [S, V/S, D]."""
    shape = (rows.shape[0], width)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * rows.shape[1]
            + start + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _vocab_ce_fwd(h, rows, labels, shards):
    """Mean CE over the T rows of ``h`` [T, D], with an online f32
    logsumexp (running max and sum) over the vocabulary chunks.

    Saves the inputs and the [T] logsumexp. Where T ≤ D the chunks' bf16
    logits, [T, V] in all, are no larger than the [D, V] head gradient,
    and are saved too: the backward then runs two head matmuls, not
    three. Otherwise the backward recomputes them chunk by chunk, and
    the loss holds one logits block at a time."""
    with jax.named_scope(scopes.VOCAB_CHUNKS):
        t, d = h.shape
        rows = rows.reshape(shards, -1, d)
        keep = t <= d

        def step(carry, start, width, _):
            m, total, gold = carry
            logits = _logits(h, jax.lax.dynamic_slice_in_dim(
                rows, start, width, 1))
            m_new = jnp.maximum(m, jnp.max(logits, axis=(1, 2)))
            total = total * jnp.exp(m - m_new) + jnp.sum(
                jnp.exp(logits - m_new[:, None, None]), axis=(1, 2))
            gold = gold + jnp.sum(jnp.where(
                _onehot(labels, _chunk_ids(rows, start, width)),
                logits, 0.0), axis=(1, 2))
            return (m_new, total, gold), (
                logits.astype(h.dtype) if keep else None)

        zeros = jnp.zeros((t,), jnp.float32)
        (m, total, gold), kept, kept_tail = _vocab_loop(
            step, (jnp.full((t,), -jnp.inf, jnp.float32), zeros, zeros),
            t, rows.shape[1], None, None)
        lse = m + jnp.log(total)
        return jnp.mean(lse - gold), (h, rows, labels, lse, kept,
                                      kept_tail)


def _vocab_ce_bwd(shards, res, g):
    """Per chunk: the logits (kept, or recomputed), ``dlogits =
    (softmax − onehot) · g/T`` in f32 cast to the compute dtype; its
    rows of the [V, D] head gradient are written once, and the [T, D]
    hidden-state gradient is summed in f32.

    The head gradient is built in the table's [V, D] layout, so a tied
    table's gradient sum reads it with no transposed copy."""
    h, rows, labels, lse, kept, kept_tail = res
    del shards
    with jax.named_scope(scopes.VOCAB_CHUNKS):
        t, d = h.shape
        scale = g / t

        def step(dh, start, width, logits):
            w_c = jax.lax.dynamic_slice_in_dim(rows, start, width, 1)
            if logits is None:
                logits = _logits(h, w_c)
            p = jnp.exp(logits.astype(jnp.float32) - lse[:, None, None])
            dlogits = ((p - _onehot(labels, _chunk_ids(rows, start, width))
                        ) * scale).astype(h.dtype)
            dw = jnp.einsum("tsv,td->svd", dlogits, h,
                            preferred_element_type=jnp.float32)
            dh = dh + jnp.einsum("tsv,svd->td", dlogits, w_c,
                                 preferred_element_type=jnp.float32)
            return dh, dw.astype(rows.dtype)

        dh, dw, dw_tail = _vocab_loop(
            step, jnp.zeros((t, d), jnp.float32), t, rows.shape[1], kept,
            kept_tail)
        dw = jnp.moveaxis(dw, 0, 1)          # [S, full, width, D]
        dw = dw.reshape(dw.shape[0], -1, d)
        if dw_tail is not None:
            dw = jnp.concatenate([dw, dw_tail], axis=1)
        return dh.astype(h.dtype), dw.reshape(-1, d), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _vocab_chunked_ce(h, rows, labels, shards):
    """h: [T, D]; rows: [V, D]; labels: [T]; ``shards`` splits the
    vocabulary -> scalar mean CE, looped over the vocabulary (T < V)."""
    return _vocab_ce_fwd(h, rows, labels, shards)[0]


_vocab_chunked_ce.defvjp(_vocab_ce_fwd, _vocab_ce_bwd)


def barlow_twins_loss(z1: jnp.ndarray, z2: jnp.ndarray,
                      lambda_offdiag: float = 5e-3) -> jnp.ndarray:
    """Redundancy-reduction loss on two embedding views [B, D].

    C = (z1_norm^T z2_norm)/B;  loss = Σ_i (1−C_ii)² + λ Σ_{i≠j} C_ij².
    """
    z1 = z1.astype(jnp.float32)
    z2 = z2.astype(jnp.float32)
    b = z1.shape[0]
    z1 = (z1 - z1.mean(0)) / (z1.std(0) + 1e-5)
    z2 = (z2 - z2.mean(0)) / (z2.std(0) + 1e-5)
    c = (z1.T @ z2) / b
    on = jnp.sum(jnp.square(1.0 - jnp.diag(c)))
    off = jnp.sum(jnp.square(c)) - jnp.sum(jnp.square(jnp.diag(c)))
    return on + lambda_offdiag * off
