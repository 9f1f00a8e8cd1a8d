"""The bridge to the system under test: the program's model of a
configuration, a check that the program stores its weights in the
layout the benchmark makes them in, and the
conversion of host span records to the trace clock."""
from __future__ import annotations

import time

from chipbench import arch, reference


def model_of(config: dict):
    """The program's model of the configuration, built from the
    architecture module's ``program_config``, its layout checked."""
    from repro.models import get_model
    model = get_model(arch.load(config).program_config(config))
    check_layout(model, config)
    return model


def check_layout(model, config: dict) -> None:
    """The program's weight tree has exactly the benchmark's leaves."""
    import jax
    want = {reference.leaf_name(path): shape
            for path, shape, _ in arch.load(config).leaves(config)}
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        got["/".join(str(k.key) for k in path)] = tuple(leaf.shape)
    if got != want:
        raise RuntimeError(f"the program's weight layout {got} is not "
                           f"the benchmark's {want}")


class SpanClock:
    """Maps the program's span records (microseconds from the tracer's
    start on ``perf_counter_ns``) onto the profiler's clock, through the
    window annotation whose start both clocks saw."""

    def __init__(self):
        self.tracer_t0 = time.perf_counter_ns()
        self.window_perf_ns = None

    def mark_window(self) -> None:
        self.window_perf_ns = time.perf_counter_ns()

    def spans(self, records, window_start_ns: int):
        off = window_start_ns - self.window_perf_ns + self.tracer_t0
        out = []
        for r in records:
            if r.get("kind") != "span":
                continue
            s = off + int(r["ts_us"] * 1e3)
            out.append((r["name"], s, s + int(r["dur_us"] * 1e3)))
        return out
