"""The whole decode step's share of its HBM roofline: the bytes a step
of the traced window needs on average (``flops.decode_step_bytes``:
every weight once, each active slot's live K/V read and its new K/V
written) at the chip's peak bandwidth, over the device time of one
execution of the decode step (``serve.decode_step_ms``). Moves
``serve_itl_p95_ms``."""
from chipbench import scopes


def read(run):
    if run["kind"] != "serve" or not run["decode_steps"]:
        return None
    found = scopes.executions(run, scopes.DECODE_MODULE)
    if found is None:
        return None
    count, seconds = found
    least = run["decode_step_bytes"] / run["decode_steps"] \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / count)
