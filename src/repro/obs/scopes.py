"""Names of the layers of the jitted training step.

Each is a ``jax.named_scope`` opened by the program at a layer
boundary, so every instruction of the compiled step carries the layer
in the ``op_name`` of its metadata; readers of a device trace map an op
to its layer through that path. The optimizer's parts nest inside
``OPTIMIZER`` and read as ``optimizer/<part>``.
"""

EMBED = "embed"                # token gather and its scatter gradient
LAYERS = "layers"              # decoder stack: forward, backward, remat
HEAD_LOSS = "head_loss"        # final norm, head and chunked CE
VOCAB_CHUNKS = "vocab_chunks"  # the CE looped over vocabulary chunks
GRAD_ACCUM = "grad_accum"      # the microbatch scan and its f32 sums
GRAD_PMEAN = "grad_pmean"      # the data-parallel all-reduce
STEP_METRICS = "step_metrics"  # gradient norm and per-layer norms
OPTIMIZER = "optimizer"        # the whole update, applied

PACK = "pack"                  # tree -> flat substrate
SEG_NORM = "seg_norm"          # segment-norm kernel
TRUST_TABLE = "trust_table"    # per-segment ratios and scales
SEG_APPLY = "seg_apply"        # apply kernel
UNPACK = "unpack"              # flat delta -> tree
APPLY_UPDATES = "apply_updates"  # params + delta

# every scope as its path in an op_name
ALL = (EMBED, LAYERS, HEAD_LOSS, f"{HEAD_LOSS}/{VOCAB_CHUNKS}",
       GRAD_ACCUM, GRAD_PMEAN, STEP_METRICS, OPTIMIZER) + tuple(
           f"{OPTIMIZER}/{part}" for part in (
               PACK, SEG_NORM, TRUST_TABLE, SEG_APPLY, UNPACK,
               APPLY_UPDATES))
