"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a topology it is
only told about, so these tests show what interpret mode cannot: that
the Pallas kernels of the main path lower to native Mosaic calls
(``tpu_custom_call``) at real widths, within the chip's VMEM and HBM.
Nothing runs, so nothing here says anything about results or times.

Only one process may hold the TPU library, so the topology is described
inside a module fixture, never at import, and every case lives in this
one file: under pytest-xdist only the worker given this file loads the
library. The persistent compilation cache is off around these compiles
(an entry compiled for a described chip cannot be read back without
one).
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.core import build_optimizer
from repro.kernels import ops
from repro.kernels.attention_decode import attention_decode_pallas
from repro.kernels.segmented_update import segmented_update_pallas
from repro.models import get_model
from repro.obs import scopes
from repro.training.train_state import TrainState
from repro.training.trainer import make_train_step

# about qwen2.5-3b's tensor count with one segment per layer tensor
# (36 layers x 12 + 4), over 64K rows of 128 lanes
SEG_ROWS = 65536
SEG_COUNT = 436


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                t = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — any failure means skip
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield t
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _native(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mode", ["lars", "paper", "lamb"])
def test_segmented_update_compiles_for_v5e(one_chip, mode, dtype):
    """Both segmented passes at the substrate's storage dtype (bf16
    with stochastic rounding on, as the ``bf16_master_sr`` policy
    runs it)."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    n_bufs = 2 if mode == "lamb" else 1
    step = functools.partial(
        segmented_update_pallas, mode=mode, eta=1e-3, weight_decay=5e-4,
        momentum=0.9, b1=0.9, b2=0.999, eps=1e-6,
        stochastic_round=dtype == "bfloat16", interpret=False)

    def f(w, g, bufs, seg_ids, adapt_mask, lr):
        return step(w, g, bufs, seg_ids=seg_ids, adapt_mask=adapt_mask,
                    base_lr=lr)

    buf = sds((SEG_ROWS, 128), dtype)
    compiled = jax.jit(f).lower(
        buf, buf, (buf,) * n_bufs, sds((SEG_ROWS, 1), jnp.int32),
        sds((SEG_COUNT,), jnp.bool_), sds((), jnp.float32)).compile()
    assert _native(compiled)


@pytest.mark.parametrize("slots,heads,kv_heads,head_dim,length,window,dtype", [
    # qwen2.5-3b decode: 8 slots x 2048 tokens, GQA 16/2
    (8, 16, 2, 128, 2048, None, "bfloat16"),
    (8, 16, 2, 128, 2048, None, "float32"),
    # gemma3-12b local layer: a 1024-token ring, head_dim 256
    (8, 16, 8, 256, 1024, 1024, "bfloat16"),
    # the qwen2.5-3b serving pool: 32 slots x 2048 tokens in bf16
    (32, 16, 2, 128, 2048, None, "bfloat16"),
])
def test_attention_decode_compiles_for_v5e(one_chip, slots, heads, kv_heads,
                                           head_dim, length, window, dtype):
    """The kernel on the merged-head pool [B, T, Hkv·Dh]: native, with
    no VMEM temporaries beyond its own blocks, and the pool aliased so
    that the append writes in place."""
    b = slots

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((b, length, kv_heads * head_dim), dtype)
    compiled = jax.jit(functools.partial(
        attention_decode_pallas, window=window, interpret=False),
        donate_argnums=(3, 4)).lower(
        sds((b, 1, heads, head_dim), jnp.bfloat16),
        sds((b, 1, kv_heads, head_dim), jnp.bfloat16),
        sds((b, 1, kv_heads, head_dim), jnp.bfloat16),
        pool, pool, sds((b,), jnp.int32)).compile()
    assert _native(compiled)
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * b * length * kv_heads \
        * head_dim * jnp.dtype(dtype).itemsize


def test_data_parallel_fused_step_compiles_for_v5e(topo, monkeypatch):
    """The D=4 shard_map train step with the fused optimizer on a 2x2
    mesh: every operand of the optimizer is replicated, and the
    compiler cannot partition a Mosaic call, so the step must run the
    update per device (``trainer._optimizer_fn``). The compiled text
    carries the step's layer scopes (``repro.obs.scopes``)."""
    # the kernels pick interpret mode from the host's backend, which
    # here is the CPU; this compile targets the described chip
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1),
                ("data", "model"))
    cfg = ModelConfig(family="dense", num_layers=2, d_model=256,
                      num_heads=4, num_kv_heads=2, d_ff=512,
                      vocab_size=1024)
    model = get_model(cfg)
    opt = build_optimizer("tvlars", total_steps=4, learning_rate=1.0,
                          use_kernel="fused")
    state = jax.eval_shape(
        lambda: TrainState.create(model.init(jax.random.PRNGKey(0)), opt))
    rep = NamedSharding(mesh, P())
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        state)
    row = jax.ShapeDtypeStruct((2, 4, 128), jnp.int32,
                               sharding=NamedSharding(mesh, P(None, "data")))
    step = make_train_step(model, opt, accum_steps=2, mesh=mesh)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        state, {"tokens": row, "labels": row}).compile()
    assert _native(compiled)
    text = compiled.as_text()
    assert "all-reduce" in text
    # every layer scope of the step, the all-reduce's among them, names
    # instructions of the compiled program, and the update's two Mosaic
    # calls sit under their own parts of the optimizer
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in scopes.ALL:
        assert any(_passes_through(n, scope) for n in names), scope
    kernels = [re.search(r'op_name="([^"]*)"', line).group(1)
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2
    for part in (scopes.SEG_NORM, scopes.SEG_APPLY):
        assert sum(_passes_through(n, f"{scopes.OPTIMIZER}/{part}")
                   for n in kernels) == 1, part


def _passes_through(op_name: str, scope: str) -> bool:
    """``op_name`` passes through each segment of ``scope`` in order,
    bare or inside a transformation (``transpose(jvp(layers))``);
    other names, such as ``shard_map``, may come between them."""
    parts = iter(re.split(r"[/()]", op_name))
    return all(any(p == seg for p in parts) for seg in scope.split("/"))
