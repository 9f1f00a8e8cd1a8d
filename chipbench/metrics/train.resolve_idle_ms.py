"""Idle time per optimizer step of the first device inside the
program's ``resolve`` annotations (the per-step metric sync of
``trainer.fit``), read on the trace's own clock. Moves
``train_tokens_per_s``."""
from chipbench import scopes, xplane


def read(run):
    if run["kind"] != "train":
        return None
    spans = xplane.host_spans(scopes.trace_file(run["logdir"]),
                              {"resolve"})
    if not spans:
        return None
    return scopes.idle_inside(run["trace"], spans) / run["steps"] * 1e3
