"""PartitionSpec rules — Megatron-style tensor parallelism with a
divisibility guard.

Params are sharded over the ``model`` axis only (replicated over
pod/data); the batch shards over ``("pod","data")``. Rules are keyed by
the leaf's path name, so they apply uniformly to params AND to optimizer
state that mirrors the param tree (momentum / mu / nu), which keeps the
whole TrainState sharded consistently.

The guard: a dim is given the ``model`` axis only when its size divides
the axis size, otherwise that dim stays replicated (DESIGN.md §4 —
e.g. whisper's 20 heads or kv=2/8 on a 16-way axis). d_model/d_ff/vocab
always divide for the assigned configs, so every tensor keeps at least
one useful sharding.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _path_names(path) -> list[str]:
    names = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            names.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            names.append(p.name)
        elif isinstance(p, jax.tree_util.SequenceKey):
            names.append(str(p.idx))
        else:
            names.append(str(p))
    return names


# (leaf name, context) -> axis-from-the-END to shard with "model"
#   e.g. wq [*, D, H, Dh] -> shard H = end-2
_END_AXIS_RULES = {
    "wq": 2, "wk": 2, "wv": 2,       # [.., D, H, Dh] -> H
    "table": 2,                       # [V, D] -> V (vocab-parallel embed)
    "head": 1,                        # [D, V] -> V
    "router": 1,                      # [D, E] -> E
    "in_proj": 1,                     # [D, X] -> X (mamba column-parallel)
    "out_proj": 2,                    # [Di, D] -> Di (row-parallel)
    "conv_w": 1,                      # [W, C] -> C (channel-parallel)
    "conv_b": 1,
}


def _leaf_model_axis(names: list[str], ndim: int) -> Optional[int]:
    """Returns the dim index (from the front) to try sharding, or None."""
    leaf = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    if leaf == "wo":
        # attn wo [.., H, Dh, D] -> H (end-3); mlp/moe wo [.., F|E.., D]
        if parent == "attn" or "attn" in parent:
            end = 3
        elif parent == "moe":
            end = 3                   # [E, F, D] -> E (expert-parallel)
        else:
            end = 2                   # [F, D] -> F (row-parallel)
    elif leaf in ("wi", "wg"):
        if parent == "moe":
            end = 3                   # [E, D, F] -> E
        else:
            end = 1                   # [D, F] -> F (column-parallel)
    elif leaf in _END_AXIS_RULES:
        end = _END_AXIS_RULES[leaf]
    else:
        return None                   # biases, norms, scalars: replicate
    if end > ndim:
        return None
    return ndim - end


def leaf_pspec(path, leaf, mesh: Mesh, *, fsdp: bool = False) -> P:
    """PartitionSpec for one param/opt-state leaf (guarded).

    ``fsdp=True`` (training) additionally shards one remaining dim over
    the (pod, data) axes — ZeRO-3-style parameter/optimizer-state
    sharding; XLA inserts the per-layer all-gathers. Required for the
    largest assigned configs (qwen2-72b f32 momentum = 290 GB — TP-only
    at 16-way leaves 18 GB/chip, over v5e's 16 GB).
    """
    shape = leaf.shape
    if len(shape) == 0:
        return P()
    m = mesh.shape.get("model", 1)
    names = _path_names(path)
    dim = _leaf_model_axis(names, len(shape))
    spec: list = [None] * len(shape)
    if dim is not None and m > 1 and shape[dim] % m == 0 and shape[dim] >= m:
        spec[dim] = "model"
    if fsdp and names[-1] not in ("table", "head"):
        # table/head stay TP-only: fsdp-sharding the unembed projection
        # makes the partitioner all-gather full f32 logits over the data
        # axis in its backward (measured +110 GiB/dev on train_4k).
        dp = _data_axes(mesh)
        dp_size = int(np.prod([mesh.shape[a] for a in dp], dtype=int)) \
            if dp else 1
        if dp and dp_size > 1:
            # largest unsharded dim divisible by the dp extent
            cands = [i for i in range(len(shape))
                     if spec[i] is None and shape[i] % dp_size == 0
                     and shape[i] >= dp_size]
            if cands:
                best = max(cands, key=lambda i: shape[i])
                spec[best] = dp
    return P(*spec)


def state_pspecs(mesh: Mesh, state_shapes: Any, *, fsdp: bool = False
                 ) -> Any:
    """PartitionSpec pytree for a TrainState/params shape tree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf_pspec(path, leaf, mesh, fsdp=fsdp),
        state_shapes)


def _data_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_pspecs(mesh: Mesh, batch_shapes: dict) -> dict:
    """Batch dims shard over (pod, data); scalars replicate."""
    dp = _data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp], dtype=int)) if dp \
        else 1

    def spec(path, leaf):
        if len(leaf.shape) == 0:
            return P()
        if dp and leaf.shape[0] % dp_size == 0 and leaf.shape[0] >= dp_size:
            return P(dp, *([None] * (len(leaf.shape) - 1)))
        return P(*([None] * len(leaf.shape)))   # tiny batch: replicate

    return jax.tree_util.tree_map_with_path(spec, batch_shapes)


def cache_pspecs(mesh: Mesh, cache_shapes: Any) -> Any:
    """KV/SSM cache sharding for decode.

    Layout conventions (see models/*): self-attention pools are
    [layers, B, T, Hkv·Dh] (k/v, heads merged), cross-attention K/V
    [layers, B, T, Hkv, Dh] (ck/cv); SSM state [.., B, H, P, N] and
    conv [.., B, W-1, C]. Batch shards over (pod,data). The model axis
    goes to Hkv when it divides, else to the sequence dim T (long-context
    global layers), else to Dh; a merged pool, whose head boundaries its
    shape does not show, takes it on T, else on Hkv·Dh.
    """
    m = mesh.shape.get("model", 1)
    dp = _data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp], dtype=int)) if dp \
        else 1

    def shard_b(out, leaf, b_dim):
        if dp and leaf.shape[b_dim] % dp_size == 0 \
                and leaf.shape[b_dim] >= dp_size:
            out[b_dim] = dp

    def spec(path, leaf):
        names = _path_names(path)
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        out: list = [None] * nd
        if names[-1] in ("k", "v"):       # [..., B, T, Hkv·Dh]
            b_dim, t_dim, h_dim = nd - 3, nd - 2, nd - 1
            shard_b(out, leaf, b_dim)
            for dim in (t_dim, h_dim):
                if m > 1 and leaf.shape[dim] % m == 0 and leaf.shape[dim] >= m:
                    out[dim] = "model"
                    break
            return P(*out)
        if names[-1] in ("ck", "cv"):
            # [..., B, T, Hkv, Dh]: model axis -> Hkv, else T, else Dh
            # (whisper: 20 heads and T=1500 both indivisible by 16, but
            # Dh=64 shards — partial scores + all-reduce over Dh).
            b_dim, t_dim, h_dim, d_dim = nd - 4, nd - 3, nd - 2, nd - 1
            shard_b(out, leaf, b_dim)
            for dim in (h_dim, t_dim, d_dim):
                if m > 1 and leaf.shape[dim] % m == 0 and leaf.shape[dim] >= m:
                    out[dim] = "model"
                    break
            return P(*out)
        if names[-1] == "state":          # [.., B, H, P, N]
            b_dim, h_dim = nd - 4, nd - 3
            shard_b(out, leaf, b_dim)
            if m > 1 and leaf.shape[h_dim] % m == 0:
                out[h_dim] = "model"
            return P(*out)
        if names[-1] == "conv":           # [.., B, W-1, C]
            b_dim, c_dim = nd - 3, nd - 1
            shard_b(out, leaf, b_dim)
            if m > 1 and leaf.shape[c_dim] % m == 0:
                out[c_dim] = "model"
            return P(*out)
        # unknown cache leaf: replicate
        return P(*out)

    return jax.tree_util.tree_map_with_path(spec, cache_shapes)


def named(mesh: Mesh, pspec_tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))
