"""Device time per optimizer step of the ops under the program's
``grad_accum`` scope and under none nested in it (the microbatch scan's
f32 gradient sums, its bookkeeping and the final divide), averaged over
the chips (``chipbench/scopes.py``). Moves ``train_tokens_per_s``."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, "grad_accum")
