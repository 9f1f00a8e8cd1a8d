"""The host's time per decode step in the traced window: the engine's
``decode`` span (the dispatch) plus its ``sample`` span (the wait for
the sampled tokens), summed and divided by the number of ``decode``
spans. Beside ``serve.decode_step_ms``, the difference is host time
that the chip idles through. Moves ``serve_itl_p95_ms``."""


def read(run):
    if run["kind"] != "serve":
        return None
    lo, hi = run["trace"].window
    inside = [(name, e - s) for name, s, e in run["spans"]
              if name in ("decode", "sample") and lo <= s and e <= hi]
    steps = sum(1 for name, _ in inside if name == "decode")
    if not steps:
        return None
    return sum(d for _, d in inside) / steps / 1e6
