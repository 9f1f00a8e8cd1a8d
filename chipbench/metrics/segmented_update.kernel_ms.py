"""Device time per optimizer step of the fused layer-wise update's
``pallas_call``s (the segment-norm and the apply kernel of
``kernels/segmented_update.py``), averaged over the chips. Moves
``train_tokens_per_s``."""
from chipbench import xplane

# a TPU trace names a Pallas call after its enclosing function; its op
# text carries the custom-call target. The two launches of the fused
# update are the training step's only Pallas calls.
KERNELS = r'custom_call_target="tpu_custom_call"'


def read(run):
    if run["kind"] != "train" or not xplane.op_count(run["trace"], KERNELS):
        return None
    return xplane.op_seconds(run["trace"], KERNELS) / run["steps"] * 1e3
