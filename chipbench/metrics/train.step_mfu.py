"""Model FLOPs of the traced window's optimizer steps (``flops.py``:
6 N_matmul + 12 L S d per token, nothing recomputed) over the window's
length times the chips times the chip's bf16 peak. Moves
``train_tokens_per_s``."""


def read(run):
    if run["kind"] != "train":
        return None
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * run["flops"] / (run["window_s"] * peak)
