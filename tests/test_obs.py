"""Observability subsystem: span tracer, trace-v1 schema, layerwise
trust-ratio telemetry, profiler windows, reporting tools, bench gate.

Covers the PR's acceptance criteria:
  * layerwise stream == the ``ref.trust_scale_table`` oracle (<= 1e-6)
    with the fused step's exactly-2-``pallas_call`` invariant intact
    while telemetry is ON;
  * trace-v1 records round-trip JsonlSink -> validate_jsonl ->
    render_trace (Perfetto-loadable) -> obs_report;
  * tracing overhead <= 3% of a real sync step loop;
  * BufferedSink keeps exact order (and re-raises writer errors) under
    mixed metric + trace load;
  * bench_compare exits nonzero exactly on regressions/missing
    entries; host_info carries git provenance.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_optimizer
from repro.core import labels as labels_lib
from repro.data.synthetic import ClassificationData, batch_iterator
from repro.diagnostics import sink as sink_lib
from repro.kernels.ops import count_pallas_calls
from repro.models.cnn import apply_mlp_classifier, init_mlp_classifier
from repro.obs import LayerwiseHistory, StepProfiler, profile
from repro.obs import layerwise as obs_layerwise
from repro.obs import trace as obs_trace
from repro.training import TrainState, classifier_task, fit
from repro.training.trainer import FitOptions, MetricRing, make_train_step

pytestmark = pytest.mark.obs

_TOOLS = pathlib.Path(__file__).resolve().parents[1] / "tools"


def _load_tool(name: str):
    spec = importlib.util.spec_from_file_location(name,
                                                  _TOOLS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _clf_setup(hidden=16, depth=2, batch=8):
    data = ClassificationData(num_classes=4, image_size=8, seed=0)
    params = init_mlp_classifier(jax.random.PRNGKey(0),
                                 in_dim=8 * 8 * 3, num_classes=4,
                                 hidden=hidden, depth=depth)
    return data, params, data.batch(jax.random.PRNGKey(1), batch)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_span_records_duration_and_attrs():
    t = obs_trace.Tracer()
    with t.span("work", step=3, probe="lanczos"):
        time.sleep(0.001)
    t.instant("mark", step=3)
    t.counter("depth", 4.0, step=3)
    recs = t.events()
    assert [r["kind"] for r in recs] == ["span", "instant", "counter"]
    span = recs[0]
    assert span["trace"] == "v1" and span["name"] == "work"
    assert span["step"] == 3 and span["probe"] == "lanczos"
    assert span["dur_us"] >= 1000.0 and span["ts_us"] >= 0.0
    assert isinstance(span["tid"], str) and span["tid"]
    assert recs[2]["value"] == 4.0


def test_ring_is_bounded_fifo():
    t = obs_trace.Tracer(capacity=4)
    for i in range(10):
        t.instant(f"e{i}")
    assert len(t) == 4
    assert [r["name"] for r in t.events()] == ["e6", "e7", "e8", "e9"]
    drained = t.drain()
    assert len(drained) == 4 and len(t) == 0


def test_disabled_tracer_records_nothing_and_shares_null_ctx():
    t = obs_trace.Tracer(enabled=False)
    ctx1 = t.span("a")
    ctx2 = t.span("b", step=1)
    assert ctx1 is ctx2                 # one shared nullcontext
    with ctx1:
        pass
    t.instant("x")
    t.counter("c", 1.0)
    assert len(t) == 0
    assert len(obs_trace.NULL) == 0


def test_enabled_tracer_is_truthy_even_when_empty():
    # __len__ alone would make an empty tracer falsy and `tracer or
    # NULL` would silently drop it (the bug class this guards)
    t = obs_trace.Tracer()
    assert len(t) == 0 and bool(t)
    assert not bool(obs_trace.NULL)


class _FakeProfiler:
    """Stands in for ``jax.profiler``'s two annotations and logs what
    is entered and left, in order."""

    def __init__(self):
        self.log = []

    def _ctx(self, *what):
        @contextlib.contextmanager
        def ctx():
            self.log.append(("enter", *what))
            yield
            self.log.append(("exit", *what))
        return ctx()

    def TraceAnnotation(self, name):  # noqa: N802 — jax's spelling
        return self._ctx("span", name)

    def StepTraceAnnotation(self, name, step_num):  # noqa: N802
        return self._ctx("step", name, step_num)


@pytest.fixture
def fake_profiler(monkeypatch):
    fake = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        fake.TraceAnnotation)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation",
                        fake.StepTraceAnnotation)
    return fake


def test_tracer_enters_one_annotation_per_span(fake_profiler):
    t = obs_trace.Tracer()
    with t.span("outer", step=0):
        with t.span("inner", step=0, probe="x"):
            pass
    t.instant("mark")
    t.counter("depth", 1.0)
    with t.step("train", 7):
        pass
    assert fake_profiler.log == [
        ("enter", "span", "outer"), ("enter", "span", "inner"),
        ("exit", "span", "inner"), ("exit", "span", "outer"),
        ("enter", "step", "train", 7), ("exit", "step", "train", 7)]
    assert [r["name"] for r in t.events()] == ["inner", "outer", "mark",
                                               "depth"]


@pytest.mark.parametrize("tracer", [
    obs_trace.NULL, obs_trace.Tracer(enabled=False)],
    ids=["NULL", "disabled"])
def test_disabled_tracer_enters_no_annotation(fake_profiler, tracer):
    with tracer.span("a", step=0):
        pass
    with tracer.step("train", 0):
        pass
    assert fake_profiler.log == []
    # the disabled path is the shared nullcontext, no clock read
    assert tracer.span("a") is tracer.step("train", 1) \
        is obs_trace.NULL.span("b")


def test_annotations_land_in_a_profiler_trace(tmp_path):
    """On the real profiler, the spans and step markers are host events
    of the trace under their own names."""
    from jax.profiler import ProfileData
    t = obs_trace.Tracer()
    f = jax.jit(lambda x: x * 2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(2):
            with t.step("train", i), t.span("resolve", step=i):
                f(1.0).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    names = [ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events]
    assert names.count("train") == 2 and names.count("resolve") == 2


def test_export_roundtrips_through_jsonl_and_validates(tmp_path):
    t = obs_trace.Tracer()
    with t.span("alpha", step=0):
        pass
    t.counter("q", 2.5, step=1)
    t.instant("nostep")                 # step defaults to 0 on export
    path = str(tmp_path / "trace.jsonl")
    with sink_lib.JsonlSink(path) as sink:
        assert t.export(sink) == 3
    assert len(t) == 0                  # export drains by default
    n, n_trace = sink_lib.validate_jsonl(path, counts=True)
    assert (n, n_trace) == (3, 3)
    recs = [json.loads(line) for line in open(path)]
    assert recs[2]["step"] == 0


@pytest.mark.parametrize("mutate", [
    lambda r: r.update(kind="bogus"),
    lambda r: r.update(name=""),
    lambda r: r.update(ts_us=-1.0),
    lambda r: r.pop("dur_us"),          # span without duration
    lambda r: r.update(trace="v2"),
])
def test_validate_jsonl_rejects_malformed_trace_records(tmp_path, mutate):
    rec = {"step": 0, "trace": "v1", "kind": "span", "name": "x",
           "ts_us": 1.0, "dur_us": 2.0, "tid": "main"}
    mutate(rec)
    path = tmp_path / "bad.jsonl"
    path.write_text(json.dumps(rec) + "\n")
    with pytest.raises(ValueError):
        sink_lib.validate_jsonl(str(path))


def test_validate_jsonl_rejects_non_numeric_counter_value(tmp_path):
    rec = {"step": 0, "trace": "v1", "kind": "counter", "name": "c",
           "ts_us": 1.0, "value": "high", "tid": "main"}
    path = tmp_path / "bad.jsonl"
    path.write_text(json.dumps(rec) + "\n")
    with pytest.raises(ValueError):
        sink_lib.validate_jsonl(str(path))


def test_phase_summary_aggregates_spans_only():
    recs = [
        {"trace": "v1", "kind": "span", "name": "a", "ts_us": 0,
         "dur_us": 100.0},
        {"trace": "v1", "kind": "span", "name": "a", "ts_us": 0,
         "dur_us": 300.0},
        {"trace": "v1", "kind": "instant", "name": "a", "ts_us": 0},
        {"step": 0, "loss": 1.0},       # plain metric record
    ]
    s = obs_trace.phase_summary(recs)
    assert set(s) == {"a"}
    assert s["a"]["count"] == 2
    assert s["a"]["total_ms"] == pytest.approx(0.4)
    assert s["a"]["mean_us"] == pytest.approx(200.0)
    assert s["a"]["max_us"] == pytest.approx(300.0)


# ---------------------------------------------------------------------------
# layerwise telemetry: oracle parity + pallas invariant
# ---------------------------------------------------------------------------

def _f32_tolerances(n: int) -> dict[str, float]:
    """Relative gap allowed between two float32 evaluations of the
    layer-wise triple that sum a segment of ``n`` elements in different
    orders (the kernel: per-row partial sums, then the segment sum; the
    tree path: one ``jnp.sum`` over the leaf). Each evaluation of
    sqrt(sum x^2) is within g/2 + u of the exact norm, with
    u = 2^-24 and g = n u / (1 - n u): the n roundings of the squares
    and the adds bound the sum's relative error by g in any order, the
    sqrt halves it and rounds once more. Two evaluations then differ by
    at most g + 2u. The ratio eta |w| / (|b| + wd |w| + eps) takes the
    errors of both norms and five roundings (two products, two adds,
    the quotient): 2 (g/2 + u) + 5u for one evaluation, twice that
    between two. (LAMB's |w| / |b| takes fewer.)"""
    u = 2.0 ** -24
    g = n * u / (1 - n * u)
    one_norm = g / 2 + u
    return {"w_norm": 2 * one_norm, "g_norm": 2 * one_norm,
            "trust_ratio": 2 * (2 * one_norm + 5 * u)}


@pytest.mark.parametrize("name", ["lars", "tvlars", "lamb"])
def test_fused_layerwise_matches_tree_oracle(name):
    """The fused kernel's surfaced (w_norm, g_norm, trust_ratio) must
    equal the pure-jnp tree path's per-leaf triples to the float32
    rounding of the segment sums — the tree path IS the ref oracle
    math, leaf by leaf, summed in another order."""
    params = {"w": jnp.linspace(0.1, 1.0, 8 * 16).reshape(8, 16),
              "b": jnp.full((16,), 0.01)}
    grads = {"w": jnp.full((8, 16), 0.3), "b": jnp.full((16,), 0.02)}
    rtol = _f32_tolerances(max(x.size for x in
                               jax.tree_util.tree_leaves(params)))
    taps = {}
    for uk in (False, "fused"):
        opt = build_optimizer(name, total_steps=10, learning_rate=0.2,
                              batch_size=8, use_kernel=uk)
        st = opt.init(params)

        def up(g, s, p):
            with obs_layerwise.capture() as tap:
                opt.update(g, s, p)
            return dict(tap)

        taps[uk] = jax.device_get(jax.jit(up)(grads, st, params))
    assert set(taps[False]) == set(obs_layerwise.METRICS)
    for k in obs_layerwise.METRICS:
        np.testing.assert_allclose(taps["fused"][k], taps[False][k],
                                   rtol=rtol[k], atol=0,
                                   err_msg=f"{name}/{k}")


def test_two_pallas_calls_with_telemetry_on():
    """Surfacing the layerwise stream must not add launches: the
    jaxpr of a layerwise=True fused train step still counts exactly 2
    pallas_calls, and the step's metrics carry the (nseg,) arrays."""
    _, params, batch = _clf_setup()
    opt = build_optimizer("lars", total_steps=10, learning_rate=0.3,
                          use_kernel="fused")
    state = TrainState.create(params, opt)
    step = make_train_step(classifier_task(apply_mlp_classifier), opt,
                           layerwise=True)
    jaxpr = jax.make_jaxpr(step)(state, *batch)
    assert count_pallas_calls(jaxpr.jaxpr) == 2
    _, metrics = jax.jit(step)(state, *batch)
    nseg = len(jax.tree_util.tree_leaves(params))
    for m in obs_layerwise.METRICS:
        assert metrics[f"layerwise/{m}"].shape == (nseg,)


def test_layerwise_absent_without_flag():
    _, params, batch = _clf_setup()
    opt = build_optimizer("lars", total_steps=10, learning_rate=0.3,
                          use_kernel="fused")
    state = TrainState.create(params, opt)
    step = make_train_step(classifier_task(apply_mlp_classifier), opt)
    _, metrics = jax.jit(step)(state, *batch)
    assert not any(k.startswith("layerwise/") for k in metrics)


def test_expand_names_and_mismatch():
    lw = {"layerwise/trust_ratio": np.array([0.5, 1.5])}
    out = obs_layerwise.expand(lw, ["a/w", "b/w"])
    assert out == {"layerwise/a/w/trust_ratio": 0.5,
                   "layerwise/b/w/trust_ratio": 1.5}
    assert obs_layerwise.expand(lw, None) == lw
    with pytest.raises(ValueError, match="segment names"):
        obs_layerwise.expand(lw, ["only_one"])


def test_layerwise_history_decimates_to_capacity():
    h = LayerwiseHistory(capacity=8)
    for i in range(1000):
        h.add(i, {"layerwise/x/trust_ratio": float(i)})
    assert len(h) <= 8
    assert h.stride == 2 ** (h.stride.bit_length() - 1)  # power of two
    assert h.steps == sorted(h.steps)
    assert h.steps[0] == 0              # early coverage survives
    assert h.steps[-1] >= 1000 - h.stride  # late coverage too


# ---------------------------------------------------------------------------
# fit integration
# ---------------------------------------------------------------------------

def _fit_layerwise(tmp_sink, **fit_kw):
    data, params, _ = _clf_setup()
    opt = build_optimizer("lars", total_steps=6, learning_rate=0.3,
                          use_kernel="fused")
    state = TrainState.create(params, opt)
    step = make_train_step(classifier_task(apply_mlp_classifier), opt,
                           layerwise=True)
    return fit(step, state, batch_iterator(data, 8), 6, sink=tmp_sink,
               layerwise_names=labels_lib.leaf_names(params), **fit_kw)


def test_fit_layerwise_every_decimates_records():
    sink = sink_lib.MemorySink()
    _, history = _fit_layerwise(sink, layerwise_every=3)
    kept = [r["step"] for r in sink.records
            if any(k.startswith("layerwise/") for k in r)]
    assert kept == [0, 3]
    # decimated steps keep their scalar metrics
    assert all("loss" in r for r in sink.records)
    # expansion produced float scalars named by segment
    rec0 = sink.records[0]
    lw_keys = [k for k in rec0 if k.startswith("layerwise/")]
    assert lw_keys and all(isinstance(rec0[k], float) for k in lw_keys)
    assert any(k.endswith("/trust_ratio") for k in lw_keys)
    assert history[0].keys() == sink.records[0].keys() - {"step"}


def test_fit_layerwise_history_receives_kept_snapshots():
    sink = sink_lib.MemorySink()
    h = LayerwiseHistory(capacity=16)
    _fit_layerwise(sink, layerwise_every=2, layerwise_history=h)
    assert h.steps == [0, 2, 4]
    assert all(any(k.endswith("/w_norm") for k in s)
               for s in h.snapshots)


@pytest.mark.parametrize("async_metrics", [0, 2])
def test_fit_traces_loop_phases(async_metrics):
    data, params, _ = _clf_setup()
    opt = build_optimizer("lars", total_steps=4, learning_rate=0.3)
    state = TrainState.create(params, opt)
    step = make_train_step(classifier_task(apply_mlp_classifier), opt)
    tracer = obs_trace.Tracer()
    fit(step, state, batch_iterator(data, 8), 4, tracer=tracer,
        async_metrics=async_metrics)
    by_name = {}
    for r in tracer.events():
        by_name.setdefault(r["name"], []).append(r)
    assert len(by_name["data_wait"]) == 4
    assert len(by_name["dispatch"]) == 4
    assert len(by_name["resolve"]) == 4   # ring drain resolves all 4
    assert [r["step"] for r in by_name["dispatch"]] == [0, 1, 2, 3]
    if async_metrics:
        assert all("in_flight" in r for r in by_name["resolve"])


@pytest.mark.parametrize("traced", [True, False])
def test_fit_marks_steps_only_when_traced(fake_profiler, traced):
    data, params, _ = _clf_setup()
    opt = build_optimizer("lars", total_steps=4, learning_rate=0.3)
    state = TrainState.create(params, opt)
    step = make_train_step(classifier_task(apply_mlp_classifier), opt)
    tracer = obs_trace.Tracer() if traced else None
    fit(step, state, batch_iterator(data, 8), 3,
        options=FitOptions(tracer=tracer))
    steps = [e for e in fake_profiler.log if e[:2] == ("enter", "step")]
    if not traced:
        assert fake_profiler.log == []
        return
    assert steps == [("enter", "step", "train", i) for i in range(3)]
    # each step's loop phases are annotated inside its step marker
    first = fake_profiler.log[:fake_profiler.log.index(
        ("exit", "step", "train", 0)) + 1]
    assert [e[2] for e in first if e[:2] == ("enter", "span")] == [
        "data_wait", "dispatch", "resolve"]


def test_metric_ring_resolve_span_counts_entries():
    tracer = obs_trace.Tracer()
    ring = MetricRing(2, tracer=tracer)
    seen = []
    for i in range(5):
        ring.append(i, jnp.float32(i),
                    lambda s, v, _l: seen.append((s, float(v))))
    ring.drain()
    assert seen == [(i, float(i)) for i in range(5)]
    spans = [r for r in tracer.events() if r["name"] == "resolve"]
    assert len(spans) == 5
    assert [r["step"] for r in spans] == [0, 1, 2, 3, 4]


def test_prefetching_stream_traces_produce_spans():
    from repro.data import pipeline
    tracer = obs_trace.Tracer()
    stream = pipeline.PrefetchingStream(iter(range(4)), size=2,
                                        tracer=tracer)
    try:
        assert [next(stream) for _ in range(4)] == [0, 1, 2, 3]
        deadline = time.time() + 2.0
        while time.time() < deadline:
            spans = [r for r in tracer.events()
                     if r["name"] == "produce"]
            if len(spans) >= 4:
                break
            time.sleep(0.01)
        assert len(spans) >= 4
        assert all(r["tid"] == "PrefetchingStream-producer"
                   for r in spans)
    finally:
        stream.close()


def test_tracing_overhead_within_budget():
    """<= 3% wall-clock delta, traced vs untraced, on a real
    pre-compiled sync step loop mirroring fit's span structure (the
    jitted step is compiled once up front so both modes time pure
    steady-state host work)."""
    data, params, _ = _clf_setup(hidden=256, depth=3, batch=64)
    opt = build_optimizer("lars", total_steps=1000, learning_rate=0.3,
                          use_kernel="fused")
    state0 = TrainState.create(params, opt)
    step = jax.jit(make_train_step(
        classifier_task(apply_mlp_classifier), opt))
    batch = data.batch(jax.random.PRNGKey(2), 64)
    jax.block_until_ready(step(state0, *batch))   # compile once

    def run(tracer, steps=30):
        state = state0
        t0 = time.perf_counter()
        for i in range(steps):
            with tracer.span("data_wait", step=i):
                b = batch
            with tracer.span("dispatch", step=i):
                state, metrics = step(state, *b)
            with tracer.span("resolve", step=i):
                jax.device_get(metrics)
        return time.perf_counter() - t0

    run(obs_trace.NULL, steps=5)                  # warm both paths
    run(obs_trace.Tracer(), steps=5)
    # span cost is ~us/step; wall-clock noise on a loaded shared CPU
    # is several ms per 30-step run, so measure off/on INTERLEAVED
    # (drift hits both modes alike), take min-of-pairs, and retry the
    # whole measurement a few times before declaring a regression.
    best = float("inf")
    for _ in range(4):
        off = min(run(obs_trace.NULL) for _ in range(3))
        on = min(run(obs_trace.Tracer()) for _ in range(3))
        best = min(best, on / off)
        if best <= 1.03:
            break
    assert best <= 1.03, (
        f"tracing overhead {best - 1:.2%} exceeds 3% budget over 4 "
        f"measurement attempts")


# ---------------------------------------------------------------------------
# BufferedSink under mixed metric + trace load
# ---------------------------------------------------------------------------

def test_buffered_sink_preserves_mixed_record_order():
    mem = sink_lib.MemorySink()
    buf = sink_lib.BufferedSink(mem, capacity=8)
    tracer = obs_trace.Tracer()
    expect = []
    for i in range(50):
        buf.write(i, {"loss": float(i)})
        expect.append(("metric", i))
        with tracer.span("s", step=i):
            pass
        tracer.export(buf)              # interleave trace records
        expect.append(("trace", i))
    buf.close()
    got = [("trace", r["step"]) if "trace" in r
           else ("metric", r["step"]) for r in mem.records]
    assert got == expect
    assert all(r["kind"] == "span" for r in mem.records
               if "trace" in r)


def test_buffered_sink_reraises_writer_error_on_caller():
    class Boom(sink_lib.MetricsSink):
        def write(self, step, metrics, *, last=False):
            if metrics.get("kind") == "span":
                raise RuntimeError("disk full")

    buf = sink_lib.BufferedSink(Boom(), capacity=4)
    buf.write(0, {"loss": 1.0})
    tracer = obs_trace.Tracer()
    tracer.instant("x")
    with tracer.span("s"):
        pass
    tracer.export(buf)
    with pytest.raises(RuntimeError, match="disk full"):
        buf.flush()


# ---------------------------------------------------------------------------
# profiler windows
# ---------------------------------------------------------------------------

def test_step_profiler_window_fires_once():
    calls = []
    prof = StepProfiler("/tmp/prof", start=2, steps=3,
                        start_fn=lambda d: calls.append(("start", d)),
                        stop_fn=lambda: calls.append(("stop",)))
    for i in range(10):
        prof.step(i)
    prof.close()
    assert calls == [("start", "/tmp/prof"), ("stop",)]
    assert not prof.running
    prof.step(2)                        # window fires at most once
    assert calls == [("start", "/tmp/prof"), ("stop",)]


def test_step_profiler_close_flushes_open_window():
    calls = []
    prof = profile("/x", start=0, steps=100,
                   start_fn=lambda d: calls.append("start"),
                   stop_fn=lambda: calls.append("stop"))
    prof.step(0)
    assert prof.running
    prof.close()
    prof.close()                        # idempotent
    assert calls == ["start", "stop"]


def test_step_profiler_validates_args():
    with pytest.raises(ValueError):
        StepProfiler("/x", steps=0)
    with pytest.raises(ValueError):
        StepProfiler("/x", start=-1)


def test_fit_drives_profiler_window():
    data, params, _ = _clf_setup()
    opt = build_optimizer("lars", total_steps=4, learning_rate=0.3)
    state = TrainState.create(params, opt)
    step = make_train_step(classifier_task(apply_mlp_classifier), opt)
    calls = []
    prof = StepProfiler("/p", start=1, steps=2,
                        start_fn=lambda d: calls.append("start"),
                        stop_fn=lambda: calls.append("stop"))
    fit(step, state, batch_iterator(data, 8), 4, profiler=prof)
    assert calls == ["start", "stop"]


# ---------------------------------------------------------------------------
# tools: render_trace / obs_report / bench_compare / host provenance
# ---------------------------------------------------------------------------

def _write_trace(tmp_path) -> str:
    t = obs_trace.Tracer()
    with t.span("dispatch", step=0):
        pass
    t.instant("switch", step=1)
    t.counter("depth", 3.0, step=1)
    path = str(tmp_path / "t.jsonl")
    with sink_lib.JsonlSink(path) as sink:
        t.export(sink)
    return path


def test_render_trace_emits_perfetto_loadable_json(tmp_path):
    rt = _load_tool("render_trace")
    src = _write_trace(tmp_path)
    out = str(tmp_path / "t.perfetto.json")
    assert rt.main([src, "-o", out]) == 0
    doc = json.load(open(out))
    events = doc["traceEvents"]
    phases = {e["ph"] for e in events}
    assert {"M", "X", "i", "C"} <= phases
    meta = [e for e in events if e["ph"] == "M"]
    assert meta[0]["name"] == "thread_name"
    span = next(e for e in events if e["ph"] == "X")
    assert span["name"] == "dispatch" and span["dur"] >= 0
    assert isinstance(span["tid"], int)
    assert span["args"]["step"] == 0


def test_render_trace_fails_on_traceless_input(tmp_path):
    rt = _load_tool("render_trace")
    src = tmp_path / "plain.jsonl"
    src.write_text('{"step": 0, "loss": 1.0}\n')
    out = str(tmp_path / "o.json")
    assert rt.main([str(src), "-o", out]) == 1


def test_obs_report_phase_and_layer_tables(tmp_path, capsys):
    rep = _load_tool("obs_report")
    trace = _write_trace(tmp_path)
    metrics = tmp_path / "m.jsonl"
    rows = [{"step": 0, "layerwise/a/w/trust_ratio": 0.9,
             "layerwise/b/w/trust_ratio": 0.2},
            {"step": 2, "layerwise/a/w/trust_ratio": 1.01,
             "layerwise/b/w/trust_ratio": 0.3}]
    metrics.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert rep.main(["--trace", trace, "--metrics", str(metrics),
                     "--top-k", "1"]) == 0
    out = capsys.readouterr().out
    assert "dispatch" in out
    # b/w's LAST ratio (0.3) is farther from 1.0 than a/w's (1.01)
    assert "b/w" in out and "a/w" not in out.split("sharpest")[1]


def test_obs_report_sharpest_uses_last_value():
    rep = _load_tool("obs_report")
    rows = [{"step": 0, "layerwise/x/trust_ratio": 5.0},
            {"step": 1, "layerwise/x/trust_ratio": 1.0},
            {"step": 1, "layerwise/y/trust_ratio": 0.5}]
    top = rep.sharpest_layers(rows, 2)
    assert top[0][0] == "y"             # |0.5-1| > |1.0-1|
    assert top[1] == ("x", 1.0, 0.0)


def test_obs_report_constants_match_library():
    # obs_report duplicates PREFIX (and path-loads trace.py) to stay
    # stdlib-only; pin the copies to the library they mirror.
    rep = _load_tool("obs_report")
    assert rep.PREFIX == obs_layerwise.PREFIX
    assert rep.phase_summary.__code__.co_code == \
        obs_trace.phase_summary.__code__.co_code


def _bench_doc(entries):
    return {"schema": "bench/v2", "suite": "kernels",
            "host": {"backend": "cpu", "jax": "0", "git_sha": "a" * 40},
            "entries": entries}


def test_bench_compare_exit_codes(tmp_path):
    bc = _load_tool("bench_compare")
    base = tmp_path / "base.json"
    cand = tmp_path / "cand.json"
    base.write_text(json.dumps(_bench_doc(
        [{"name": "k1", "us_per_call": 100.0},
         {"name": "k2", "us_per_call": 50.0}])))
    # within threshold (+20% < 50%) and a faster entry -> OK
    cand.write_text(json.dumps(_bench_doc(
        [{"name": "k1", "us_per_call": 120.0},
         {"name": "k2", "us_per_call": 40.0},
         {"name": "k3", "us_per_call": 1.0}])))
    assert bc.main([str(base), str(cand)]) == 0
    # regression past the threshold -> 1
    cand.write_text(json.dumps(_bench_doc(
        [{"name": "k1", "us_per_call": 200.0},
         {"name": "k2", "us_per_call": 50.0}])))
    assert bc.main([str(base), str(cand)]) == 1
    # tighter threshold flips a small slowdown into a failure
    cand.write_text(json.dumps(_bench_doc(
        [{"name": "k1", "us_per_call": 120.0},
         {"name": "k2", "us_per_call": 50.0}])))
    assert bc.main([str(base), str(cand), "--threshold", "0.1"]) == 1
    # a dropped bench entry is itself a regression -> 1
    cand.write_text(json.dumps(_bench_doc(
        [{"name": "k1", "us_per_call": 100.0}])))
    assert bc.main([str(base), str(cand)]) == 1
    # bad schema -> 1
    cand.write_text(json.dumps({"schema": "bench/v1", "entries": []}))
    assert bc.main([str(base), str(cand)]) == 1


def test_host_info_carries_provenance():
    import sys
    sys.path.insert(0, str(_TOOLS.parent))
    try:
        from benchmarks import common
    finally:
        sys.path.pop(0)
    info = common.host_info()
    assert info["jax"] and "jaxlib" in info
    # this test runs inside the checkout, so git provenance must be
    # present and well-formed
    assert isinstance(info["git_sha"], str) and len(info["git_sha"]) == 40
    assert isinstance(info["git_dirty"], bool)


def test_smoke_trace_schema_validates_itself(tmp_path):
    from repro.diagnostics import smoke
    smoke.run(str(tmp_path), steps=2, probe_every=2, num_iters=2)
    tp = tmp_path / "trace_smoke.jsonl"
    assert tp.exists()
    _, n_trace = sink_lib.validate_jsonl(str(tp), counts=True)
    assert n_trace >= 6
