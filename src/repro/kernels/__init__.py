# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# Here: the layer-wise optimizer step (the paper's per-tensor hot loop)
# plus the serving decode hot path.
#   lars_update.py        per-tensor fused LARS step (2 pallas_calls/leaf)
#   segmented_update.py   whole-tree segmented step  (2 pallas_calls/step)
#   rmsnorm.py            fused RMSNorm (activation-path exemplar)
#   attention_decode.py   fused serving decode: reads only the live K/V
#                         of a merged-head pool, mask-from-pos +
#                         online-softmax GQA (1 call/layer)
#   ref.py                pure-jnp oracles + shared layer-wise math
#   ops.py                dispatch (TPU native / interpret / REPRO_FORCE_REF)
