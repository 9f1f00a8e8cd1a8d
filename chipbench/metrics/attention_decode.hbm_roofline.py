"""The fused decode-attention kernel's share of its HBM roofline: the
bytes its calls in the traced window need (each active slot's live K/V
context, its query and its output, per layer; ``flops.py``) at the
chip's peak bandwidth, over the kernel's device time. Moves
``serve_itl_p95_ms``."""
from chipbench import xplane

# a TPU trace names a Pallas call after its enclosing function; its op
# text carries the custom-call target. The decode kernel is the engine's
# only Pallas call.
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(run):
    if run["kind"] != "serve" or not xplane.op_count(run["trace"], KERNEL):
        return None
    least = run["decode_bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / xplane.op_seconds(run["trace"], KERNEL)
