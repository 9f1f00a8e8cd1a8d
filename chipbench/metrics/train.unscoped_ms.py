"""Device time per optimizer step of the train step's ops under none of
the program's scopes (what the compiler emitted without a scope in its
metadata), averaged over the chips (``chipbench/scopes.py``). Moves
``train_tokens_per_s``."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, None)
