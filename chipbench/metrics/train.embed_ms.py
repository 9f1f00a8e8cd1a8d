"""Device time per optimizer step of the ops under the program's
``embed`` scope (the token gather and its scatter gradient), averaged
over the chips (``chipbench/scopes.py``). Moves ``train_tokens_per_s``."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, "embed")
