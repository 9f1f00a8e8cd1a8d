"""Device time per optimizer step of the ops under the program's
``optimizer`` scope with its parts (pack, segment-norm kernel, trust
table, apply kernel, unpack, the parameter add), averaged over the
chips (``chipbench/scopes.py``). Moves ``train_tokens_per_s``."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, "optimizer")
