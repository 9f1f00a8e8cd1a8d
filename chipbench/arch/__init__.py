"""The architectures of the benchmark's configurations, one module each.

``load(config)`` imports ``chipbench/arch/<model_type>.py``, named by the
``model_type`` key that every configuration file carries (as the
published ``config.json`` does). Everything that depends on the shape of
the model lives in that module; the reference, the FLOP and byte counts
and the cell runners reach it only through ``load``. A configuration of
a new architecture joins with a new module and leaves every file here
as it is.

A module defines, for a configuration ``config`` (the parsed file):

``program_config(config)``
    The program's ``repro.configs.base.ModelConfig``, family included.
``leaves(config)``
    ``[(path, shape, role), ...]`` of every weight the program stores,
    in a fixed order; ``path`` is a tuple of keys into the weight tree.
    ``role`` is ``"adapt"`` (a trust-ratio group of TVLARS, with weight
    decay), ``"plain"`` (the plain base rate, no decay) or ``"held"``
    (stored and compared, never touched by the optimizer; only
    ``after_step`` changes it).
``init_weights(config, dtype)``
    ``key -> weights``: every leaf drawn from the key (called under
    jit), in the program's layout and ``dtype``.
``row_loss(config, mm, params, row)``
    ``(loss, aux)``: the plain float32 mean next-token cross-entropy of
    one row of ``seq + 1`` token ids, every contraction through
    ``mm(equation, x, y)``; ``aux`` is a dict of arrays, summed over the
    rows of a step and handed to ``after_step``. The module may compute
    the loss in blocks so that it fits.
``after_step(config, params, aux)``
    The weights after the update of the ``held`` leaves, which follows
    each optimizer step (for a model with none: ``params``).
``matmul_params_per_token(config)``
    The weights that enter a matrix product once per token (a tied head
    counted once; for experts, only those a token is routed to).
``attention_flops_per_token(config, context)``
    Forward FLOPs of attention for one token over ``context`` positions,
    all layers (``q.k`` and ``p.v``); linear in ``context``.
``param_count(config)``
    Every stored parameter.

Serving cells also use:

``logits(config, mm, params, ids)``
    ``[S] -> [S, V]`` float32 logits of one sequence.
``head_params(config)``
    The matrix weights used only where a logit is needed.
``kv_bytes_per_token(config, itemsize)``
    K and V of one token over all layers.
``decode_io_bytes(config, itemsize)``
    The query read and the output written by the decode attention for
    one token, all layers.
"""
from __future__ import annotations

import importlib
import re
from types import ModuleType

from chipbench.common import BenchError


def load(config: dict) -> ModuleType:
    """The module of ``config``'s ``model_type``."""
    kind = config.get("model_type")
    if not isinstance(kind, str) or not re.fullmatch(r"[A-Za-z_]\w*", kind):
        raise BenchError(f"configuration {config.get('name')!r} has no "
                         f"usable model_type ({kind!r})")
    name = f"{__name__}.{kind}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise BenchError(f"no architecture module for model_type {kind!r}: "
                         f"add chipbench/arch/{kind}.py") from None


def roles(config: dict) -> list[str]:
    """The role of every leaf, in ``leaves`` order."""
    return [role for _, _, role in load(config).leaves(config)]
