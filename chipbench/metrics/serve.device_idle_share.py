"""Share of the traced window in which no op ran on the device: the
engine's host work between steps (admission, the per-step token sync,
per-slot bookkeeping) and any wait for arrivals. Moves
``serve_itl_p95_ms``."""


def read(run):
    if run["kind"] != "serve":
        return None
    return 100.0 * (1.0 - run["busy_s"] / run["window_s"])
