"""The layer-wise update's share of its HBM roofline: the bytes the
update needs (``flops.update_bytes``: read weight, gradient and
momentum, write momentum and weight, at their storage widths) at the
chip's peak bandwidth, over the device time per step of the update's
``pallas_call``s (found by their scopes ``optimizer/seg_norm`` and
``optimizer/seg_apply``). Moves ``train_tokens_per_s``."""
from chipbench import scopes

KERNELS = ("optimizer/seg_norm", "optimizer/seg_apply")


def read(run):
    found = scopes.pallas_calls(run, KERNELS)
    if not found or not found[0]:
        return None
    per_step = found[1] / run["steps"]
    least = run["update_bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / per_step
