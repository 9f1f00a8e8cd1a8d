"""Pallas-kernel micro-benchmarks.

On this CPU container the kernels run in interpret mode, so wall-time is
NOT indicative of TPU performance — the relevant numbers are (a) the
modeled HBM traffic (derived columns) and (b) the ``pallas_calls``
launch counts, which are exact and backend-independent: the segmented
substrate path issues exactly 2 per optimizer STEP regardless of leaf
count — that launch collapse is the whole point of the flat substrate
(``core/flatten.py`` + ``kernels/segmented_update.py``).

Sections:
  * optimizer-step dispatch sweep over model-registry param trees:
    pure-jnp vs ``use_kernel="fused"``
    under each precision policy (f32 / bf16_master), reporting us/step,
    pallas_call counts, resident substrate state bytes and the modeled
    per-step HBM traffic (``segmented_update.modeled_hbm_bytes``) —
    plus a ``state_traffic_ratio`` summary row per (tree, optimizer)
    evidencing the bf16 policy's >=1.8x optimizer-state-bytes win at an
    unchanged 2-``pallas_call`` count.

Every ``record()``ed row is also flushed to
``experiments/bench/BENCH_kernels.json`` (``--json-name`` to rename,
``--quick`` for a reduced CI-friendly sweep) so future PRs can regress
against the trajectory machine-readably.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import peak_temp_bytes, record, time_fn, write_json
from repro.configs.base import ModelConfig
from repro.core import apply_updates, build_optimizer
from repro.core.layerwise import storage_dtype
from repro.data.pipeline import stack_microbatches
from repro.data.synthetic import lm_batch
from repro.kernels.ops import count_pallas_calls
from repro.kernels.segmented_update import modeled_hbm_bytes
from repro.models import get_model
from repro.training.train_state import TrainState, opt_buffer_bytes
from repro.training.trainer import make_train_step

# build_optimizer name -> segmented-kernel mode (for the traffic model)
_MODES = {"wa-lars": "lars", "tvlars": "paper", "lamb": "lamb"}


def _param_trees() -> dict:
    """Small versions of the registry families' param-tree SHAPES —
    realistic leaf counts/mixes at CPU-benchable sizes."""
    trees = {}
    for name, family, kw in [
        ("dense-2l", "dense", {}),
        ("moe-2l", "moe", dict(num_experts=4, experts_per_token=2)),
    ]:
        cfg = ModelConfig(family=family, num_layers=2, d_model=64,
                          num_heads=2, num_kv_heads=2, d_ff=128,
                          vocab_size=128, remat=False, **kw)
        trees[name] = get_model(cfg).init(jax.random.PRNGKey(0))
    return trees


def bench_optimizer_dispatch(quick: bool = False) -> None:
    rng = np.random.default_rng(0)
    trees = _param_trees()
    if quick:
        trees = {"dense-2l": trees["dense-2l"]}
    for tree_name, params in trees.items():
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(rng.normal(size=p.shape), p.dtype),
            params)
        leaves = jax.tree_util.tree_leaves(params)
        n_leaves = len(leaves)
        n_adapt = sum(1 for p in leaves if p.ndim >= 2)
        for opt_name in ("wa-lars", "tvlars", "lamb"):
            per_precision = {}   # precision -> modeled state bytes/step
            for uk, prec, label in (
                    (False, "f32", "jnp"),
                    ("fused", "f32", "fused"),
                    ("fused", "bf16_master", "fused_bf16_master")):
                opt = build_optimizer(opt_name, total_steps=100,
                                      learning_rate=0.2, use_kernel=uk,
                                      precision=prec)
                state = TrainState.create(params, opt)

                def step(g, s):
                    u, os_ = opt.update(g, s.opt_state, s.params)
                    return TrainState(s.step + 1,
                                      apply_updates(s.params, u), os_)

                n_pallas = count_pallas_calls(
                    jax.make_jaxpr(step)(grads, state).jaxpr)
                us = time_fn(jax.jit(step), grads, state)
                fields = dict(pallas_calls=n_pallas, leaves=n_leaves,
                              adapt=n_adapt, precision=prec,
                              opt_state_bytes=opt_buffer_bytes(state))
                if uk == "fused":
                    # substrate rows from the first flat state buffer
                    rows = jax.tree_util.tree_leaves(
                        state.opt_state)[1].shape[0]
                    hbm = modeled_hbm_bytes(
                        _MODES[opt_name], rows,
                        itemsize=jnp.dtype(storage_dtype(prec)).itemsize)
                    fields.update(substrate_rows=rows,
                                  hbm_state_bytes=hbm["state"],
                                  hbm_total_bytes=hbm["total"])
                    per_precision[prec] = (hbm, n_pallas)
                record(f"kernels/opt_step/{tree_name}/{opt_name}/{label}",
                       us, **fields)
            if len(per_precision) == 2:
                f32, bf16 = per_precision["f32"], \
                    per_precision["bf16_master"]
                record(
                    f"kernels/opt_step/{tree_name}/{opt_name}/"
                    f"state_traffic_ratio", 0.0,
                    state_traffic_ratio=round(
                        f32[0]["state"] / bf16[0]["state"], 3),
                    total_traffic_ratio=round(
                        f32[0]["total"] / bf16[0]["total"], 3),
                    pallas_calls_f32=f32[1], pallas_calls_bf16=bf16[1])


def bench_accumulation(quick: bool = False) -> None:
    """Gradient-accumulation sweep: global batch = K × fixed microbatch.

    The claim under test: with the accumulating step a global batch ≥8×
    the device microbatch runs at FIXED peak memory (XLA temp bytes stay
    flat as K grows, while the naive big-batch step's grow with the
    global batch), and the fused substrate still applies the optimizer
    in exactly 2 ``pallas_call``s per *global* step regardless of K.
    """
    cfg = ModelConfig(family="dense", num_layers=2, d_model=64, num_heads=2,
                      num_kv_heads=2, d_ff=128, vocab_size=128, remat=False)
    model = get_model(cfg)
    micro, seq = 8, 32
    opt = build_optimizer("wa-lars", total_steps=100, learning_rate=0.2,
                          use_kernel="fused")
    state = TrainState.create(model.init(jax.random.PRNGKey(0)), opt)
    key = jax.random.PRNGKey(1)
    for k in (1, 4) if quick else (1, 4, 8, 16):
        g = micro * k
        toks, labels = lm_batch(key, g, seq, cfg.vocab_size)
        batch = {"tokens": toks, "labels": labels}
        # naive: one device pass over the whole global batch
        naive = make_train_step(model, opt)
        naive_peak = peak_temp_bytes(naive, state, batch)
        # accumulating: K scanned microbatches, one optimizer apply;
        # compile once (AOT) and reuse for both memory stats and timing
        stacked = batch if k == 1 else stack_microbatches(batch, k)
        step = make_train_step(model, opt, accum_steps=k)
        n_pallas = count_pallas_calls(
            jax.make_jaxpr(step)(state, stacked).jaxpr)
        compiled = jax.jit(step).lower(state, stacked).compile()
        stats = compiled.memory_analysis()
        peak = int(stats.temp_size_in_bytes) if stats is not None else -1
        us = time_fn(compiled, state, stacked)
        record(f"kernels/accum_step/global{g}_micro{micro}_k{k}", us,
               pallas_calls=n_pallas, peak_temp_bytes=peak,
               naive_peak_temp_bytes=naive_peak)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep (one tree, short accumulation "
                         "ladder) for CI")
    ap.add_argument("--json-name", default="BENCH_kernels",
                    help="basename of the JSON written to "
                         "experiments/bench/")
    args = ap.parse_args()
    bench_optimizer_dispatch(quick=args.quick)
    bench_accumulation(quick=args.quick)
    path = write_json(args.json_name, suite="kernels",
                      extra={"interpret_mode":
                                 jax.default_backend() == "cpu"})
    print(f"json -> {path}")


if __name__ == "__main__":
    main()
