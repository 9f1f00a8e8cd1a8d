"""Mean lateness of the load generator: submit time minus due time of
the requests submitted in the traced window, so that a starved
generator is not read as a fast server. Moves ``serve_ttft_p95_ms``."""


def read(run):
    if run["kind"] != "serve" or not run["arrival_lag_s"]:
        return None
    lags = run["arrival_lag_s"]
    return sum(lags) / len(lags) * 1e3
