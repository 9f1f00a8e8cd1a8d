#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Loads the cell named in BENCHMARK.json, makes its weights and inputs
from the seed, warms up every shape it uses (all of it counted as
``setup_s``), measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON line last on
stdout. ``--trace 0`` reports the cell's end-to-end metrics; ``--trace
1`` traces a short window with the profiler and reports the per-layer
metrics, read by ``chipbench/metrics/<name>.py``. Without a TPU, or
with fewer chips than the cell needs, it exits non-zero and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import common  # noqa: E402


def traced_metrics(spec: dict, w: dict, result: dict, reader: dict) -> None:
    """Reduce the run's trace and read each per-layer metric."""
    from chipbench import xplane
    files = glob.glob(os.path.join(reader["logdir"], "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise common.BenchError(f"no trace under {reader['logdir']}")
    tr = xplane.load(max(files, key=os.path.getmtime))
    reader.update(trace=tr, window_s=tr.window_s, busy_s=xplane.busy_s(tr),
                  spans=reader["clock"].spans(reader["records"],
                                              tr.window[0]),
                  peaks=common.peaks(result["device"]["kind"]))
    metrics = {}
    for m in common.metrics_of(spec, w["name"], trace=True):
        value = common.read_metric(m["name"], reader)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"]["busy_s"] = reader["busy_s"]
    result["device"]["window_s"] = reader["window_s"]
    idle = xplane.idle_by_host(tr, reader["spans"])
    result["breakdown"] = {
        "device_ops": xplane.top_ops(tr),
        "idle_gaps": [[k, v] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:10]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = common.load_spec()
    w, config, traffic = common.cell(spec, args.workload)
    common.setup_src_path()
    common.enable_compile_cache()
    try:
        devices = common.require_chips(w["chips"])
    except common.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    if traffic["kind"] == "train":
        from chipbench import train as runner
    else:
        from chipbench import serve as runner
    result, check, reader = runner.run(config, traffic, args.seed,
                                       args.seconds, bool(args.trace),
                                       devices, T_START)
    if args.trace:
        traced_metrics(spec, w, result, reader)
    common.emit(result, check)
    return 0


if __name__ == "__main__":
    sys.exit(main())
