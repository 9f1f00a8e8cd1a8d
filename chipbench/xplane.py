"""Reduction of a profiler trace (``*.xplane.pb``) to device numbers.

``load`` reads the ops that ran on each TPU device inside the traced
window (the host annotation ``WINDOW`` that the cell runners put around it)
and the host events. On that one clock:

* busy time: the union of the op intervals of a device;
* op time: summed durations of the ops whose label matches a pattern;
* idle gaps: the stretches of the window with no op running, each named
  by the host span that covers most of it;
* exposed collective time: the part of the collectives' intervals in
  which no other op runs on that device.

Run ``python chipbench/xplane.py <file.xplane.pb>`` to print the planes,
lines and a sample of ops of a trace.
"""
from __future__ import annotations

import re
import sys
from typing import NamedTuple, Optional

WINDOW = "chipbench_window"
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|all_reduce")


class Op(NamedTuple):
    start: int        # ns, on the trace clock
    end: int
    name: str
    label: str        # name and the op's string stats, for matching


class Trace(NamedTuple):
    window: tuple[int, int]
    devices: dict[int, list[Op]]      # device id -> ops in the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def _stats(ev) -> list[str]:
    out = []
    for item in ev.stats:
        try:
            _, value = item
        except (TypeError, ValueError):
            continue
        if isinstance(value, str):
            out.append(value)
    return out


def instruction(text: str) -> str:
    """The HLO instruction name of an op event (its name on TPU is the
    instruction's text, ``%fusion.3 = bf16[...] fusion(...), ...``)."""
    m = re.match(r"%?([\w.\-]+) = ", text)
    return m.group(1) if m else text


def _device_id(plane_name: str) -> Optional[int]:
    m = re.match(r"/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def load(path: str, window: str = WINDOW) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    span = None
    raw: dict[int, list[Op]] = {}
    for plane in pd.planes:
        dev = _device_id(plane.name)
        for line in plane.lines:
            if dev is None:
                for ev in line.events:
                    if ev.name == window:
                        s = int(ev.start_ns)
                        span = (s, s + int(ev.duration_ns))
                continue
            if line.name != OP_LINE:
                continue
            ops = raw.setdefault(dev, [])
            for ev in line.events:
                s = int(ev.start_ns)
                ops.append(Op(s, s + int(ev.duration_ns),
                              instruction(ev.name),
                              " ".join([ev.name] + _stats(ev))))
    if span is None:
        raise ValueError(f"{path}: no host event {window!r}")
    return Trace(span, {d: clip(ops, span) for d, ops in raw.items()})


def host_spans(path: str, names) -> list[tuple[str, int, int]]:
    """(name, start, end) of the host events with one of ``names``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if _device_id(plane.name) is not None:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns)))
    return out


def clip(ops: list[Op], window: tuple[int, int]) -> list[Op]:
    lo, hi = window
    out = []
    for op in ops:
        s, e = max(op.start, lo), min(op.end, hi)
        if e > s:
            out.append(op._replace(start=s, end=e))
    return sorted(out)


def union(intervals) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def covered(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def busy_s(trace: Trace) -> float:
    """Busy seconds, averaged over the devices traced."""
    if not trace.devices:
        return 0.0
    total = sum(covered((o.start, o.end) for o in ops)
                for ops in trace.devices.values())
    return total / len(trace.devices) / 1e9


def op_seconds(trace: Trace, pattern: str) -> float:
    """Summed duration of matching ops, averaged over the devices."""
    rx = re.compile(pattern)
    if not trace.devices:
        return 0.0
    total = sum(o.end - o.start for ops in trace.devices.values()
                for o in ops if rx.search(o.label))
    return total / len(trace.devices) / 1e9


def op_count(trace: Trace, pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(1 for ops in trace.devices.values() for o in ops
               if rx.search(o.label))


def exposed_collective_s(trace: Trace) -> float:
    """Per device, the time collectives run while no other op does;
    averaged over the devices."""
    if not trace.devices:
        return 0.0
    total = 0
    for ops in trace.devices.values():
        coll = union((o.start, o.end) for o in ops
                     if COLLECTIVE.search(o.label))
        other = union((o.start, o.end) for o in ops
                      if not COLLECTIVE.search(o.label))
        total += sum(e - s for s, e in coll) - _overlap(coll, other)
    return total / len(trace.devices) / 1e9


def _overlap(a, b) -> int:
    i = j = n = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            n += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return n


def gaps(trace: Trace, device: int) -> list[tuple[int, int]]:
    """Idle stretches of one device inside the window."""
    lo, hi = trace.window
    out, t = [], lo
    for s, e in union((o.start, o.end) for o in trace.devices[device]):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_by_host(trace: Trace, spans) -> dict[str, float]:
    """Idle seconds of the first device, split by the host span (name,
    start, end on the trace clock) that covers most of each gap;
    ``"none"`` where no span does."""
    if not trace.devices:
        return {}
    dev = min(trace.devices)
    out: dict[str, float] = {}
    lo, hi = trace.window
    spans = sorted((x for x in spans if x[2] > lo and x[1] < hi),
                   key=lambda x: x[1])
    for gs, ge in gaps(trace, dev):
        best, name = 0, "none"
        for sn, ss, se in spans:
            if ss >= ge:
                break
            ov = min(ge, se) - max(gs, ss)
            if ov > best:
                best, name = ov, sn
        out[name] = out.get(name, 0.0) + (ge - gs) / 1e9
    return out


def op_name(op: Op) -> str:
    """A stable name for grouping: the op name without its ``.N``."""
    return re.sub(r"\.\d+$", "", op.name)


# ops whose interval holds the ops of their body
CONTROL_FLOW = ("while", "conditional", "call")


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` op names with the most device time (seconds, averaged
    over the devices); control-flow ops, which hold their bodies' ops,
    are left out."""
    acc: dict[str, int] = {}
    for ops in trace.devices.values():
        for o in ops:
            name = op_name(o)
            if name in CONTROL_FLOW:
                continue
            acc[name] = acc.get(name, 0) + o.end - o.start
    k = max(len(trace.devices), 1)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in top]


def dump(path: str, sample: int = 5) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:sample]:
                print(f"    {ev.name!r} {ev.start_ns} {ev.duration_ns} "
                      f"{_stats(ev)[:6]}")


if __name__ == "__main__":
    dump(sys.argv[1])
