"""Shared plumbing of the chip benchmark: the spec files, the device
check, the compile cache, the peak table, metric readers and the result
line.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell, its configuration (``chipbench/configs/<config>.json``) and its
traffic mix (``chipbench/workloads/<traffic>.json``); each per-layer
metric is read by ``chipbench/metrics/<metric>.py``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from typing import Any, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SPEC = os.path.join(ROOT, "BENCHMARK.json")
CACHE_DIR = os.path.join(HERE, ".cache")
JAX_CACHE = os.path.join(CACHE_DIR, "jax")
TRACE_DIR = os.path.join(CACHE_DIR, "trace")


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, bad spec)."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    return load_json(SPEC)


def cell(spec: dict, name: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration file, traffic file) of one cell."""
    for w in spec["workloads"]:
        if w["name"] == name:
            break
    else:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(HERE, "workloads",
                                     f"{w['traffic']}.json"))
    return w, config, traffic


def metrics_of(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metric entries this cell reports in a run of this kind."""
    ends = [m for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])]
    if not trace:
        return ends
    moved = {m["name"] for m in ends}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in moved]


def setup_src_path() -> None:
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, for every program however quick to compile."""
    import jax
    os.makedirs(JAX_CACHE, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", JAX_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return JAX_CACHE


def require_chips(chips: int):
    """The devices the cell runs on; raises without enough TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (device 0 is "
                         f"{devs[0].platform!r}); there is no CPU fallback")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def peaks(device_kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"chipbench/peaks.json")
    return table[device_kind]


def memory_peak(devices) -> Optional[int]:
    """Peak bytes in use on the fullest of ``devices``."""
    peak = None
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return peak


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default rule)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def read_metric(name: str, run: dict) -> Optional[float]:
    """Run ``chipbench/metrics/<name>.py``'s ``read(run)``; None when
    it finds nothing to read."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


class Check:
    """The numbers compared against their limits."""

    def __init__(self):
        self.items: dict[str, dict] = {}
        self.notes: list[str] = []

    def number(self, name: str, value: float, limit: float) -> None:
        self.items[name] = {"value": value, "limit": limit}

    def require(self, ok: bool, what: str) -> None:
        if not ok:
            self.notes.append(what)

    @property
    def correct(self) -> bool:
        return not self.notes and all(
            math.isfinite(v["value"]) and v["value"] <= v["limit"]
            for v in self.items.values())

    def report(self) -> dict:
        out = dict(self.items)
        if self.notes:
            out["faults"] = "; ".join(self.notes)
        return out


def emit(result: dict, check: Check) -> None:
    """Print the numbers compared on stderr, then the result line as
    the last line of stdout (the comparison under its own key, last)."""
    for name, v in check.items.items():
        print(f"check {name}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    for note in check.notes:
        print(f"check fault: {note}", file=sys.stderr)
    print(f"check correct: {check.correct}", file=sys.stderr, flush=True)
    line = dict(result)
    line["correct"] = check.correct
    line["checks"] = check.report()
    print(json.dumps(line), flush=True)
