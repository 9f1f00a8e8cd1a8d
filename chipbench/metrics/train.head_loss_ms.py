"""Device time per optimizer step of the ops under the program's
``head_loss`` scope (final norm, tied head and chunked cross-entropy,
forward and backward), averaged over the chips
(``chipbench/scopes.py``). Moves ``train_tokens_per_s``."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, "head_loss")
