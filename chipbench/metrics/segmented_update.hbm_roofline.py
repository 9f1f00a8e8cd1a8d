"""The layer-wise update's share of its HBM roofline: the bytes the
update needs (``flops.update_bytes``: read weight, gradient and
momentum, write momentum and weight, at their storage widths) at the
chip's peak bandwidth, over the device time of the update's
``pallas_call``s per step. Moves ``train_tokens_per_s``."""
from chipbench import xplane

# a TPU trace names a Pallas call after its enclosing function; its op
# text carries the custom-call target. The two launches of the fused
# update are the training step's only Pallas calls.
KERNELS = r'custom_call_target="tpu_custom_call"'


def read(run):
    if run["kind"] != "train" or not xplane.op_count(run["trace"], KERNELS):
        return None
    per_step = xplane.op_seconds(run["trace"], KERNELS) / run["steps"]
    least = run["update_bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / per_step
