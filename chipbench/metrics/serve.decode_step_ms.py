"""Device time per execution of the engine's jitted decode step (the
module ``scopes.DECODE_MODULE`` on the trace's ``XLA Modules`` line):
the union of the ops inside each execution that lies wholly in the
traced window, averaged over the executions and the chips. Prefill, the
K/V inserts and the host's work between steps are not in it. Moves
``serve_itl_p95_ms``."""
from chipbench import scopes


def read(run):
    if run["kind"] != "serve":
        return None
    found = scopes.executions(run, scopes.DECODE_MODULE)
    if found is None:
        return None
    count, seconds = found
    return seconds / count * 1e3
