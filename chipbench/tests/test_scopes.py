"""The reduction of a trace to the program's layer scopes
(``chipbench/scopes.py``), on a hand-written compiled module with
hand-made ops, and on the small trace recorded on a TPU v5e
(``record_xplane.py``).

    python3 -m pytest chipbench/tests -q      # by hand; not in tier-1
"""
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import common, scopes, xplane  # noqa: E402

RECORDED = os.path.join(HERE, "data", "window.xplane.pb")
KNOWN = ("embed", "layers", "head_loss", "grad_accum", "optimizer",
         "optimizer/seg_norm", "optimizer/seg_apply")
STEP = "jit_train_step(42)"


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _field(num: int, value) -> bytes:
    data = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(data)) + data


def _instruction(name, opcode, op_name=None) -> bytes:
    body = _field(1, name) + _field(2, opcode)
    if op_name is not None:
        body += _field(7, _field(2, op_name))
    return body


def _hlo_proto(*computations) -> bytes:
    module = b"".join(_field(3, b"".join(_field(2, i) for i in comp))
                      for comp in computations)
    return _field(1, module)


# a compiled step: the entry with a scan, the scan's body, a fusion
PROTO = _hlo_proto(
    [_instruction("p.1", "parameter", "state"),
     _instruction("while.3", "while", "jit(train_step)/grad_accum/while"),
     _instruction("seg_norm.1", "custom-call",
                  "jit(train_step)/optimizer/shard_map/seg_norm/"
                  "pallas_call"),
     _instruction("add_fusion", "fusion", "jit(train_step)/optimizer/add"),
     _instruction("copy.7", "copy"),
     _instruction("reduce.2", "reduce", "reduce_sum")],
    [_instruction("convolution_add_fusion", "fusion",
                  "jit(train_step)/grad_accum/while/body/closed_call/"
                  "transpose(jvp(layers))/while/body/dot_general"),
     _instruction("gather.1", "gather",
                  "jit(train_step)/grad_accum/while/body/closed_call/"
                  "jvp(embed)/jit(_take)/gather"),
     _instruction("add.9", "add",
                  "jit(train_step)/grad_accum/while/body/add")])


def op(s, e, name):
    return xplane.Op(s, e, name, name)


def test_instructions_of_a_module():
    table = scopes.instructions(PROTO)
    assert table["seg_norm.1"] == (
        "custom-call",
        "jit(train_step)/optimizer/shard_map/seg_norm/pallas_call")
    assert table["copy.7"] == ("copy", "")
    assert len(table) == 9


@pytest.mark.parametrize("op_name,scope", [
    # innermost wins: layers inside grad_accum
    ("jit(train_step)/grad_accum/while/body/closed_call/"
     "transpose(jvp(layers))/while/body/dot_general", "layers"),
    ("jit(train_step)/grad_accum/while/body/add", "grad_accum"),
    # a nested path wins over its outer scope, with names between
    ("jit(train_step)/optimizer/shard_map/seg_norm/pallas_call",
     "optimizer/seg_norm"),
    ("jit(train_step)/optimizer/add", "optimizer"),
    # a part outside its parent is not that scope
    ("jit(train_step)/seg_norm/pallas_call", None),
    ("jit(train_step)/head_loss_x/dot_general", None),
    ("reduce_sum", None),
    ("", None),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name, KNOWN) == scope


def test_seconds_by_scope_on_hand_made_ops():
    ops = [op(0, 5, "p.1"),                     # before the step: not its
           op(10, 90, "while.3"),               # control flow: left out
           op(12, 40, "convolution_add_fusion"),
           op(40, 44, "gather.1"),
           op(44, 46, "add.9"),
           op(50, 60, "seg_norm.1"),
           op(60, 63, "add_fusion"),
           op(63, 70, "copy.7"),                # no metadata: unscoped
           op(70, 71, "reduce.2")]              # unknown name: unscoped
    tr = xplane.Trace((0, 100), {0: ops, 1: ops})
    runs = {d: [(2, 8, "jit_batch(7)"), (10, 95, STEP)] for d in (0, 1)}
    by = scopes.seconds_by_scope(tr, runs, {STEP: scopes.instructions(
        PROTO)}, KNOWN)
    assert by == pytest.approx({
        "layers": 28e-9, "embed": 4e-9, "grad_accum": 2e-9,
        "optimizer/seg_norm": 10e-9, "optimizer": 3e-9, None: 8e-9})


def test_an_op_missing_from_the_module_raises():
    tr = xplane.Trace((0, 100), {0: [op(12, 40, "fusion.99")]})
    runs = {0: [(10, 95, STEP)]}
    with pytest.raises(ValueError, match="fusion.99"):
        scopes.seconds_by_scope(tr, runs, {STEP: scopes.instructions(
            PROTO)}, KNOWN)
    # nor may the step's module be missing from the trace
    with pytest.raises(ValueError):
        scopes.seconds_by_scope(tr, runs, {}, KNOWN)


def test_recorded_trace_carries_its_compiled_module(monkeypatch):
    mods = scopes.hlo_modules(RECORDED)
    assert list(mods) == ["jit__lambda(15526583290487371244)"]
    table = mods["jit__lambda(15526583290487371244)"]
    assert table["convolution_tanh_fusion"] == (
        "fusion", "jit(<lambda>)/dot_general")
    assert table["copy-start"] == ("copy-start", "")
    runs = scopes.module_runs(RECORDED)
    assert [r[2] for r in runs[0]] == list(mods) * 2
    # the ops of the second call (inside the window) are all the
    # module's, and none is under a scope
    monkeypatch.setattr(scopes, "STEP_MODULE", r"^jit__lambda\(")
    tr = xplane.load(RECORDED)
    got = list(scopes.step_ops(tr, runs, mods))
    assert [o.name for _, o, _ in got] == [o.name for o in tr.devices[0]]
    assert scopes.seconds_by_scope(tr, runs, mods, KNOWN) == {
        None: xplane.busy_s(tr)}


def _run(tmp_path, steps=2):
    shutil.copy(RECORDED, tmp_path / "window.xplane.pb")
    return {"kind": "train", "logdir": str(tmp_path), "steps": steps,
            "trace": xplane.load(RECORDED)}


def test_readers_on_the_recorded_trace(tmp_path, monkeypatch):
    run = _run(tmp_path)
    # no train step ran in the recorded trace: every scope reads 0
    assert common.read_metric("train.layers_ms", run) == 0.0
    assert common.read_metric("optimizer.update_ms", run) == 0.0
    monkeypatch.setattr(scopes, "STEP_MODULE", r"^jit__lambda\(")
    run = _run(tmp_path)
    assert common.read_metric("train.unscoped_ms", run) == pytest.approx(
        xplane.busy_s(run["trace"]) / 2 * 1e3)
    with pytest.raises(ValueError, match="no scope"):
        scopes.ms_per_step(run, "layer")


def test_readers_report_nothing_for_a_program_without_scopes(
        tmp_path, monkeypatch):
    import repro.obs
    monkeypatch.delattr(repro.obs, "scopes")
    monkeypatch.setitem(sys.modules, "repro.obs.scopes", None)
    run = _run(tmp_path)
    for name in ("train.embed_ms", "train.layers_ms",
                 "train.head_loss_ms", "train.grad_accum_ms",
                 "optimizer.update_ms", "train.unscoped_ms",
                 "segmented_update.kernel_ms",
                 "segmented_update.hbm_roofline"):
        assert common.read_metric(name, run) is None
    # nor is there a resolve annotation in the recorded trace
    assert common.read_metric("train.resolve_idle_ms", run) is None


def test_idle_inside_an_annotation():
    """The recorded trace's ``host_wait`` annotation plays ``resolve``:
    the device idles through the 30 ms sleep, except for the start of
    the second call's ops, which the device clock puts before the
    sleep's end (it runs about 1.2 ms behind the host's)."""
    tr = xplane.load(RECORDED)
    wait = xplane.host_spans(RECORDED, {"host_wait"})
    (_, s, e), = wait
    inside = scopes.idle_inside(tr, wait)
    busy_inside = sum(min(o.end, e) - max(o.start, s)
                      for o in tr.devices[0] if o.end > s and o.start < e)
    assert busy_inside > 0
    assert inside == (e - s - busy_inside) / 1e9
    # the idle time before and after the annotation is not its
    assert inside < tr.window_s - xplane.busy_s(tr)


def test_resolve_reader_reads_idle_per_step(tmp_path, monkeypatch):
    run = _run(tmp_path, steps=3)
    real = xplane.host_spans
    wait = real(RECORDED, {"host_wait"})
    # the recorded annotation stands in for the program's ``resolve``
    monkeypatch.setattr(xplane, "host_spans", lambda path, names: [
        ("resolve", s, e) for _, s, e in wait] if "resolve" in names
        else real(path, names))
    assert common.read_metric("train.resolve_idle_ms", run) == \
        pytest.approx(scopes.idle_inside(run["trace"], wait) / 3 * 1e3)


# a step with the optimizer's two Pallas calls and one of the model's own
# (a grouped expert matmul, say), which the update's readers must not count
KERNEL_PROTO = _hlo_proto(
    [_instruction("seg_norm.1", "custom-call",
                  "jit(train_step)/optimizer/seg_norm/pallas_call"),
     _instruction("seg_apply.2", "custom-call",
                  "jit(train_step)/optimizer/shard_map/seg_apply/"
                  "pallas_call"),
     _instruction("experts.3", "custom-call",
                  "jit(train_step)/grad_accum/while/body/layers/experts/"
                  "pallas_call"),
     _instruction("add_fusion", "fusion",
                  "jit(train_step)/optimizer/seg_apply/add")])
PALLAS_TEXT = ' = f32[8] custom-call(), custom_call_target="tpu_custom_call"'


def _kernel_run(ops, steps=2):
    tr = xplane.Trace((0, 100), {0: ops, 1: ops})
    runs = {d: [(0, 100, STEP)] for d in (0, 1)}
    return {"kind": "train", "steps": steps, "trace": tr,
            "update_bytes": 10.0, "peaks": {"hbm_bytes_per_s": 1e9},
            "compiled": (runs, {STEP: scopes.instructions(KERNEL_PROTO)})}


def test_update_readers_count_only_the_optimizer_kernels():
    ops = [xplane.Op(0, 10, "seg_norm.1", "%seg_norm.1" + PALLAS_TEXT),
           xplane.Op(10, 30, "add_fusion", "%add_fusion = fusion()"),
           xplane.Op(30, 50, "seg_apply.2", "%seg_apply.2" + PALLAS_TEXT),
           xplane.Op(50, 90, "experts.3", "%experts.3" + PALLAS_TEXT)]
    run = _kernel_run(ops)
    assert scopes.pallas_calls(run, ("optimizer/seg_norm",
                                     "optimizer/seg_apply")) == \
        (4, pytest.approx(30e-9))
    # 30 ns over 2 steps; the expert kernel's 40 ns and the fusion under
    # seg_apply are not the update's
    assert common.read_metric("segmented_update.kernel_ms", run) == \
        pytest.approx(15e-6)
    assert common.read_metric("segmented_update.hbm_roofline", run) == \
        pytest.approx(100.0 * (10.0 / 1e9) / 15e-9)
    # a step without the update's kernels reads nothing
    run = _kernel_run(ops[3:])
    assert common.read_metric("segmented_update.kernel_ms", run) is None
    assert common.read_metric("segmented_update.hbm_roofline", run) is None


# a serving window: two decode executions inside it, a prefill between
# them, and a third decode cut by the window's end
DECODE = "jit__decode_fn(4242)"


def _serve_run():
    ops = [op(2, 10, "while.1"),          # the decode's layer loop ...
           op(4, 9, "fusion.2"),          # ... and an op of its body
           op(12, 18, "attention_decode.3"),
           op(20, 29, "fusion.7"),        # the prefill's
           op(30, 45, "fusion.2"),
           op(47, 60, "copy.1"),          # no module: host-driven insert
           op(96, 100, "while.1")]
    runs = {d: [(2, 20, DECODE), (20, 30, "jit__prefill_fn(77)"),
                (30, 46, DECODE), (96, 104, DECODE)] for d in (0, 1)}
    return {"kind": "serve", "trace": xplane.Trace((0, 100),
                                                   {0: ops, 1: ops}),
            "module_runs": runs, "decode_steps": 2,
            "decode_step_bytes": 2 * 7.25,
            "peaks": {"hbm_bytes_per_s": 1e9},
            "spans": [("decode", 1, 3), ("sample", 3, 19),
                      ("prefill", 19, 29), ("decode", 29, 31),
                      ("sample", 31, 46), ("decode", 98, 102)]}


def test_decode_executions_count_only_the_decode_module():
    run = _serve_run()
    # (8 + 6) ns in the first execution, 15 in the second, per device;
    # the prefill's op, the insert and the cut execution are not counted
    assert scopes.executions(run, scopes.DECODE_MODULE) == \
        (2, pytest.approx(29e-9))
    assert common.read_metric("serve.decode_step_ms", run) == \
        pytest.approx(14.5e-6)
    # 7.25 bytes a step at 1 GB/s take 7.25 ns; a step takes 14.5 ns
    assert common.read_metric("serve.decode_hbm_roofline", run) == \
        pytest.approx(50.0)
    # decode + sample spans inside the window, per decode span
    assert common.read_metric("serve.decode_span_ms", run) == \
        pytest.approx((2 + 16 + 2 + 15) / 2 / 1e6)


def test_decode_readers_report_nothing_without_a_decode():
    run = _serve_run()
    run["module_runs"] = {d: [(20, 30, "jit__prefill_fn(77)")]
                          for d in (0, 1)}
    run["spans"] = []
    for name in ("serve.decode_step_ms", "serve.decode_hbm_roofline",
                 "serve.decode_span_ms"):
        assert common.read_metric(name, run) is None
    # nor do they read a training run
    assert common.read_metric("serve.decode_step_ms", {"kind": "train"}) \
        is None
