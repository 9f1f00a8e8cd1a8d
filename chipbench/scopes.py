"""The training step's device time by the program's own layer names.

The program opens a ``jax.named_scope`` at each layer boundary of its
jitted step (the names are ``repro.obs.scopes``), so every instruction
of the compiled step carries its layer in the ``op_name`` of its
metadata. A TPU trace names each device op by its HLO instruction, and
its ``/host:metadata`` plane holds the optimized HLO module of every
executable it ran (an ``HloProto`` per module, under the module's name
as the ``XLA Modules`` line shows it). So, per device: every op that
runs inside a train-step execution, found by time on the ``XLA Modules``
line, maps through its module's instructions to the innermost known
scope of its ``op_name``, and its device time is summed there. An op
whose ``op_name`` names no known scope, or which has none, is unscoped.

The program also writes its host spans into the trace as annotations
(``repro.obs.trace``); ``idle_inside`` reads the device's idle time
under them on the trace's own clock.

With a program that names no scopes (no ``repro.obs.scopes``) the
reduction returns None and its readers report nothing.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Iterator, Optional

from chipbench import xplane

MODULE_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
# the executions of the jitted step ``trainer.fit`` runs
STEP_MODULE = r"^jit_train_step\("
# ops whose interval holds the ops of their bodies
CONTROL_OPCODES = ("while", "conditional", "call")
# a TPU trace names a Pallas call after its enclosing function; its op
# text carries the custom-call target
PALLAS_CALL = r'custom_call_target="tpu_custom_call"'

def known_scopes() -> Optional[tuple]:
    """The program's scope paths; None when it names none."""
    try:
        from repro.obs import scopes
    except ImportError:
        return None
    return scopes.ALL


def trace_file(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no trace under {logdir}")
    return max(files, key=os.path.getmtime)


# ---------------------------------------------------------------- protos

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def fields(buf: bytes) -> Iterator[tuple[int, object]]:
    """(field number, value) of a serialized protobuf message: an int
    for varints, bytes for everything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not handled")
        yield num, value


def _first(buf: bytes, num: int, default=b""):
    for f, v in fields(buf):
        if f == num:
            return v
    return default


def instructions(hlo_proto: bytes) -> dict[str, tuple[str, str]]:
    """``{instruction: (opcode, op_name)}`` of every computation of an
    ``HloProto`` (field 1 its ``HloModuleProto``: computations 3,
    their instructions 2, each with name 1, opcode 2 and metadata 7,
    whose ``op_name`` is 2)."""
    out = {}
    module = _first(hlo_proto, 1)
    for f, comp in fields(module):
        if f != 3:
            continue
        for g, ins in fields(comp):
            if g != 2:
                continue
            name = opcode = op_name = ""
            for h, v in fields(ins):
                if h == 1:
                    name = v.decode()
                elif h == 2:
                    opcode = v.decode()
                elif h == 7:
                    op_name = _first(v, 2).decode()
            out[name] = (opcode, op_name)
    return out


def _map_entries(buf: bytes, num: int) -> Iterator[bytes]:
    """Values of a protobuf ``map<int64, Message>`` field."""
    for f, entry in fields(buf):
        if f == num:
            yield _first(entry, 2)


def hlo_modules(path: str) -> dict[str, dict[str, tuple[str, str]]]:
    """``{module: instructions}`` from the trace's metadata plane: the
    XSpace's planes (1), by name (2); their event metadata (4), each
    named (2) with stats (5); a stat's name is in the plane's stat
    metadata (5), the ``Hlo Proto`` stat's value is bytes (6)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for f_num, plane in fields(space):
        if f_num != 1 or _first(plane, 2).decode() != METADATA_PLANE:
            continue
        stat_names = {}
        for meta in _map_entries(plane, 5):
            stat_names[_first(meta, 1, 0)] = _first(meta, 2).decode()
        for meta in _map_entries(plane, 4):
            name = _first(meta, 2).decode()
            for g, stat in fields(meta):
                if g != 5:
                    continue
                if stat_names.get(_first(stat, 1, 0)) == HLO_PROTO_STAT:
                    out[name] = instructions(_first(stat, 6))
    return out


def module_runs(path: str) -> dict[int, list[tuple[int, int, str]]]:
    """Per device, the (start, end, module) of each execution on its
    ``XLA Modules`` line, in order."""
    from jax.profiler import ProfileData
    out: dict[int, list] = {}
    for plane in ProfileData.from_file(path).planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        runs = out.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name == MODULE_LINE:
                for ev in line.events:
                    s = int(ev.start_ns)
                    runs.append((s, s + int(ev.duration_ns), ev.name))
        runs.sort()
    return out


# ---------------------------------------------------------------- scopes

def scope_of(op_name: str, known) -> Optional[str]:
    """The innermost scope of ``known`` that the ``op_name`` passes
    through: the one whose last segment comes latest in it, taking
    ``transpose(jvp(layers))`` as ``layers``. A nested path such as
    ``optimizer/seg_norm`` needs its segments in that order, other
    names (``shard_map``) may come between; it wins over ``seg_norm``
    alone. None where the ``op_name`` passes through no scope."""
    parts = [p for p in re.split(r"[/()]", op_name) if p]
    paths = sorted((k.split("/") for k in known), key=len, reverse=True)
    for i in range(len(parts) - 1, -1, -1):
        for segs in paths:
            if segs[-1] == parts[i] and _in_order(segs[:-1], parts[:i]):
                return "/".join(segs)
    return None


def _in_order(segs, parts) -> bool:
    it = iter(parts)
    return all(any(p == s for p in it) for s in segs)


def step_ops(trace: xplane.Trace, runs, modules):
    """(device, op, module) of each op that starts inside a train-step
    execution, control-flow ops left out. Raises if an op is not an
    instruction of its module: the text is another executable's."""
    rx = re.compile(STEP_MODULE)
    for dev, ops in trace.devices.items():
        mine = [r for r in runs.get(dev, []) if rx.match(r[2])]
        starts = [r[0] for r in mine]
        for op in ops:
            k = bisect.bisect_right(starts, op.start) - 1
            if k < 0 or op.start >= mine[k][1]:
                continue
            module = mine[k][2]
            table = modules.get(module)
            if table is None or op.name not in table:
                raise ValueError(f"op {op.name!r} of {module} is not in "
                                 f"that module's compiled text")
            if table[op.name][0] in CONTROL_OPCODES:
                continue
            yield dev, op, module


def seconds_by_scope(trace: xplane.Trace, runs, modules,
                     known) -> dict[Optional[str], float]:
    """Device seconds of the train-step ops (``step_ops``) by innermost
    scope (None: unscoped), averaged over the devices."""
    acc: dict[Optional[str], int] = {}
    for _, op, module in step_ops(trace, runs, modules):
        scope = scope_of(modules[module][op.name][1], known)
        acc[scope] = acc.get(scope, 0) + op.end - op.start
    k = max(len(trace.devices), 1)
    return {s: ns / k / 1e9 for s, ns in acc.items()}


def compiled(run: dict) -> tuple[dict, dict]:
    """(``module_runs``, ``hlo_modules``) of a traced run's trace file,
    kept in the run's reader inputs for the next reader."""
    if "compiled" not in run:
        path = trace_file(run["logdir"])
        run["compiled"] = (module_runs(path), hlo_modules(path))
    return run["compiled"]


def reduction(run: dict) -> Optional[dict[Optional[str], float]]:
    """``seconds_by_scope`` of a traced training run, kept in the run's
    reader inputs for the next reader; None when the program names no
    scopes."""
    known = known_scopes()
    if run["kind"] != "train" or known is None:
        return None
    if "seconds_by_scope" not in run:
        runs, modules = compiled(run)
        run["seconds_by_scope"] = seconds_by_scope(run["trace"], runs,
                                                   modules, known)
    return run["seconds_by_scope"]


def ms_per_step(run: dict, prefix: Optional[str]) -> Optional[float]:
    """Device ms per step of the scope ``prefix`` and the scopes nested
    in it (None: the unscoped ops)."""
    by = reduction(run)
    if by is None:
        return None
    if prefix is not None and prefix not in known_scopes():
        raise ValueError(f"the program names no scope {prefix!r}")
    if prefix is None:
        total = by.get(None, 0.0)
    else:
        total = sum(v for s, v in by.items() if s is not None
                    and (s == prefix or s.startswith(prefix + "/")))
    return total / run["steps"] * 1e3


def pallas_calls(run: dict, names) -> Optional[tuple[int, float]]:
    """(count, device seconds averaged over the devices) of the train
    step's Pallas calls (``PALLAS_CALL`` in the op's text) whose
    innermost scope is one of ``names`` or nested in one; None when the
    program names no scopes. A model's own kernels, under other scopes,
    are not counted."""
    known = known_scopes()
    if run["kind"] != "train" or known is None:
        return None
    missing = [n for n in names if n not in known]
    if missing:
        raise ValueError(f"the program names no scope {missing!r}")
    runs, modules = compiled(run)
    rx = re.compile(PALLAS_CALL)
    count = ns = 0
    for _, op, module in step_ops(run["trace"], runs, modules):
        if not rx.search(op.label):
            continue
        scope = scope_of(modules[module][op.name][1], known)
        if scope is not None and any(
                scope == n or scope.startswith(n + "/") for n in names):
            count += 1
            ns += op.end - op.start
    return count, ns / max(len(run["trace"].devices), 1) / 1e9


# ----------------------------------------------------------- annotations

def idle_inside(trace: xplane.Trace, spans) -> float:
    """Idle seconds of the first device inside the union of the host
    spans (name, start, end on the trace clock)."""
    if not trace.devices:
        return 0.0
    held = xplane.union((s, e) for _, s, e in spans)
    idle = xplane.gaps(trace, min(trace.devices))
    return xplane._overlap(idle, held) / 1e9
