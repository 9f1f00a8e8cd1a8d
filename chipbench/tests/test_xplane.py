"""The trace reduction, on a small trace recorded on a TPU v5e
(``record_xplane.py``) and on hand-made op intervals.

    python3 -m pytest chipbench/tests -q      # by hand; not in tier-1
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench import xplane  # noqa: E402

RECORDED = os.path.join(HERE, "data", "window.xplane.pb")


def op(s, e, name="fusion.1"):
    return xplane.Op(s, e, xplane.instruction(name), name)


def test_recorded_trace():
    tr = xplane.load(RECORDED)
    assert list(tr.devices) == [0]
    # the window annotation as the host recorded it
    assert tr.window == (46416477, 79010344)
    # inside it the second call's four ops; the first call's ops carry
    # device times 1.25 ms before their host dispatch and fall before
    # the window's start (the device and host clocks of a trace agree
    # to about a millisecond)
    names = [o.name for o in tr.devices[0]]
    assert names == ["copy-start", "copy-done", "convolution_tanh_fusion",
                     "fusion"]
    assert xplane.busy_s(tr) == (13 + 2 + 89953 + 90911) / 1e9
    assert xplane.op_seconds(tr, r"^%convolution_tanh") == 89953 / 1e9
    assert xplane.op_count(tr, r"^%fusion = ") == 1
    top = dict(xplane.top_ops(tr))
    assert set(top) == set(names)
    # the 30 ms host sleep is the gap the device idles through
    wait = xplane.host_spans(RECORDED, {"host_wait"})
    assert wait == [("host_wait", 47487577, 78032274)]
    idle = xplane.idle_by_host(tr, wait)
    # every gap overlaps the sleep most (the last one by 1.15 ms, since
    # the device clock runs behind the host's), so all idle time is its
    assert idle == {"host_wait": tr.window_s - xplane.busy_s(tr)}


def test_busy_is_a_union():
    tr = xplane.Trace((0, 100), {0: [op(10, 30), op(20, 40), op(60, 70)]})
    assert xplane.busy_s(tr) == 40 / 1e9
    assert xplane.gaps(tr, 0) == [(0, 10), (40, 60), (70, 100)]


def test_idle_gaps_named_by_covering_span():
    tr = xplane.Trace((0, 100), {0: [op(10, 30), op(60, 70)]})
    spans = [("dispatch", 25, 45), ("data_wait", 45, 65),
             ("resolve", 70, 100)]
    # gap 0-10: no span; 30-60: data_wait covers 15, dispatch 15 ->
    # the first with the most; 70-100: resolve
    assert xplane.idle_by_host(tr, spans) == {
        "none": 10 / 1e9, "dispatch": 30 / 1e9, "resolve": 30 / 1e9}


def test_exposed_collective_time():
    ops = [op(0, 50, "%fusion.2 = f32[] fusion()"),
           op(40, 80, "%all-reduce.1 = f32[] all-reduce()"),
           op(90, 100, "%all-reduce-done = f32[] all-reduce-done()")]
    tr = xplane.Trace((0, 100), {0: ops, 1: ops})
    # 30 of the first all-reduce's 40 and all 10 of the second
    assert xplane.exposed_collective_s(tr) == 40 / 1e9


def test_clip_to_window():
    tr = xplane.Trace((0, 100), {0: xplane.clip(
        [op(-20, 10), op(50, 150), op(200, 300)], (0, 100))})
    assert xplane.busy_s(tr) == 60 / 1e9


def test_top_ops_leave_out_control_flow():
    tr = xplane.Trace((0, 100), {0: [op(0, 100, "%while.3 = () while()"),
                                     op(10, 30), op(40, 50, "%fusion.2 = x")]})
    assert xplane.top_ops(tr) == [["fusion", 30 / 1e9]]


def test_instruction_name():
    assert xplane.instruction(
        "%custom-call.12 = f32[8] custom-call(f32[8] %p)") == "custom-call.12"
    assert xplane.instruction("fusion") == "fusion"
