"""Device time per optimizer step of the ops under the program's
``layers`` scope (the decoder stack's scan: forward, backward and the
remat recompute), averaged over the chips (``chipbench/scopes.py``).
Moves ``train_tokens_per_s``."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, "layers")
