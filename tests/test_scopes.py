"""The layer scopes of the jitted training step (``repro.obs.scopes``).

Every layer boundary of the step opens a ``jax.named_scope``, so the
compiled step's instructions carry their layer in the ``op_name`` of
their metadata (through ``while`` bodies and under ``transpose(jvp)``),
which is how a device trace's ops are mapped to layers. Checked on the
CPU at a tiny size: every scope names some instruction, the two
``pallas_call``s of the fused update sit under their own scopes, and
the optimized program differs only in its metadata.
"""
from __future__ import annotations

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig
from repro.core import build_optimizer
from repro.models import get_model
from repro.obs import scopes
from repro.training.train_state import TrainState
from repro.training.trainer import make_train_step

pytestmark = pytest.mark.obs


def passes_through(op_name: str, scope: str) -> bool:
    """``op_name`` passes through each segment of ``scope`` in order,
    bare or inside a transformation (``transpose(jvp(layers))``);
    other names, such as ``shard_map``, may come between them."""
    parts = iter(re.split(r"[/()]", op_name))
    return all(any(p == seg for p in parts) for seg in scope.split("/"))


def op_names(hlo_text: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def _tiny_step(accum_steps=2):
    cfg = ModelConfig(family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=256, tie_embeddings=True, remat=True)
    model = get_model(cfg)
    opt = build_optimizer("tvlars", total_steps=4, learning_rate=1.0,
                          use_kernel="fused")
    state = TrainState.create(model.init(jax.random.PRNGKey(0)), opt)
    ids = jax.random.randint(jax.random.PRNGKey(1), (accum_steps, 2, 17),
                             0, cfg.vocab_size)
    batch = {"tokens": ids[..., :-1], "labels": ids[..., 1:]}
    step = make_train_step(model, opt, accum_steps=accum_steps)
    return step, state, batch


def _pallas_name_stacks(jaxpr, stack=""):
    """The name stack of every ``pallas_call`` in ``jaxpr`` and the
    jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        here = "/".join(x for x in (stack, str(eqn.source_info.name_stack))
                        if x)
        if eqn.primitive.name == "pallas_call":
            yield here
            continue
        for v in eqn.params.values():
            for j in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: hasattr(x, "eqns")
                    or hasattr(x, "jaxpr")):
                if hasattr(j, "eqns"):
                    yield from _pallas_name_stacks(j, here)
                elif hasattr(j, "jaxpr"):
                    yield from _pallas_name_stacks(j.jaxpr, here)


def test_every_scope_names_instructions_of_the_compiled_step():
    step, state, batch = _tiny_step()
    text = jax.jit(step).lower(state, batch).compile().as_text()
    names = op_names(text)
    # the data-parallel all-reduce exists only on a mesh
    # (tests/test_tpu_compile.py compiles that step for four chips)
    for scope in set(scopes.ALL) - {scopes.GRAD_PMEAN}:
        assert any(passes_through(n, scope) for n in names), scope


def test_update_kernels_sit_under_their_scopes():
    step, state, batch = _tiny_step()
    stacks = list(_pallas_name_stacks(jax.make_jaxpr(step)(state,
                                                           batch).jaxpr))
    assert len(stacks) == 2
    norm, apply = stacks
    assert passes_through(norm, f"{scopes.OPTIMIZER}/{scopes.SEG_NORM}")
    assert passes_through(apply, f"{scopes.OPTIMIZER}/{scopes.SEG_APPLY}")


def _instructions(hlo_text: str) -> list[str]:
    """The instruction lines of a compiled module, metadata left out."""
    return [re.sub(r",? metadata=\{[^}]*\}", "", line)
            for line in hlo_text.splitlines()
            if re.match(r"^\s*(ROOT )?%|^ENTRY|^HloModule", line)]


def test_scopes_change_nothing_but_metadata(monkeypatch):
    """The step compiled with every named scope made a no-op is the
    same optimized program, instruction for instruction."""
    step, state, batch = _tiny_step()

    def compiled():
        return jax.jit(lambda s, b: step(s, b)).lower(
            state, batch).compile().as_text()

    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled()
    assert scoped != bare
    assert _instructions(scoped) == _instructions(bare)


def test_jnp_oracle_path_is_scoped_as_well(monkeypatch):
    """Under ``REPRO_FORCE_REF=1`` the update has no kernels; its ops
    still sit under the optimizer's scopes."""
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    step, state, batch = _tiny_step()
    names = op_names(jax.jit(step).lower(state, batch).compile().as_text())
    for scope in (scopes.OPTIMIZER, f"{scopes.OPTIMIZER}/{scopes.PACK}",
                  f"{scopes.OPTIMIZER}/{scopes.UNPACK}"):
        assert any(passes_through(n, scope) for n in names), scope
    assert jnp.isfinite(jax.jit(step)(state, batch)[1]["loss"])
