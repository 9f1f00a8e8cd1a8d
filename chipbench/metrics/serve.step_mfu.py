"""Model FLOPs of the engine steps in the traced window (``flops.py``:
prompt tokens through the layers with causal attention and the head at
the last position, decoded tokens through the layers and the head with
attention over their live context) over the window's length times the
chip's bf16 peak. Moves ``serve_itl_p95_ms``."""


def read(run):
    if run["kind"] != "serve" or not run["steps"]:
        return None
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * run["flops"] / (run["window_s"] * peak)
