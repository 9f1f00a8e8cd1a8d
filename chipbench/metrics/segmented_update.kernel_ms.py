"""Device time per optimizer step of the fused layer-wise update's
``pallas_call``s (the segment-norm and the apply kernel of
``kernels/segmented_update.py``, found by their scopes
``optimizer/seg_norm`` and ``optimizer/seg_apply``), averaged over the
chips. Moves ``train_tokens_per_s``."""
from chipbench import scopes

KERNELS = ("optimizer/seg_norm", "optimizer/seg_apply")


def read(run):
    found = scopes.pallas_calls(run, KERNELS)
    if not found or not found[0]:
        return None
    return found[1] / run["steps"] * 1e3
