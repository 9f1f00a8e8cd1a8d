"""Mean ``prefill`` span of the engine in the traced window: one batched
prefill, ending in the host read of the first tokens, so it holds the
device time. Moves ``serve_ttft_p95_ms``."""


def read(run):
    if run["kind"] != "serve":
        return None
    lo, hi = run["trace"].window
    spans = [(e - s) for name, s, e in run["spans"]
             if name == "prefill" and lo <= s and e <= hi]
    if not spans:
        return None
    return sum(spans) / len(spans) / 1e6
