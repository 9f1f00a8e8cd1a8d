#!/usr/bin/env python3
"""Readings that set a cell's limits: the control and the planted
faults, on the chip at the cell's own size.

    python3 chipbench/control.py --workload <cell> --seeds 11 12 13

Training cells: the plain reference in float32 against, in the
program's place, (a) the reference computed in float8 (every matrix
product's operands rounded to e4m3, the step below the bfloat16 the
configuration computes in), (b) the reference that leaves out half of
each step's rows (of a step of one row, half of its tokens) and takes
the mean over the rest, and, on a cell that
spans chips, (c) the reference that keeps only the first device's rows,
as each device does when the gradient exchange is left out. A step
that returns its state unchanged reads 1 on ``update_norm_gap`` by its
definition and needs no run.

Serving cells: a run of the cell (its window at the cell's own load),
then, over the requests its check samples, the program's widest logit
gap and the control's: at each served position the token the float8
reference puts first, read against the float32 reference. Both go
through the cell's own comparison, each with its ``correct``.

Each reading is one JSON line. The benchmark's own runs never call
this.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import common  # noqa: E402


def train_control(config: dict, traffic: dict, seeds) -> None:
    from chipbench import reference, train
    hp = train.hyper(config, traffic)
    K, mb, D = (traffic["accum_steps"], traffic["microbatch"],
                traffic["data_parallel"])
    rows, S = K * mb * D, traffic["seq_len"]
    steps = traffic["checked_steps"]
    variants = {"fp8": ("fp8", None)}
    if rows > 1:
        variants["half_batch"] = ("f32", lambda i, r: r[: r.shape[0] // 2])
    else:
        # one row a step: half of its tokens left out, the mean taken
        # over the rest
        variants["half_batch"] = ("f32",
                                  lambda i, r: r[:, : r.shape[1] // 2 + 1])
    if D > 1:
        # microbatch k holds rows [k*D*mb, (k+1)*D*mb); device 0 holds the
        # first mb of each
        first = [k * D * mb + j for k in range(K) for j in range(mb)]
        variants["no_exchange"] = ("f32", lambda i, r: r[first, :])
    for seed in seeds:
        key = reference.base_key(seed)
        rows_of = train.reference_rows(config, key, rows, S)
        t = time.perf_counter()
        ref = reference.train_readings(config, hp, key, rows_of, S, steps,
                                       "f32")
        print(json.dumps({"seed": seed, "variant": "reference",
                          "seconds": time.perf_counter() - t,
                          "losses": ref["losses"]}), flush=True)
        for name, (prec, keep) in variants.items():
            got = reference.train_readings(config, hp, key, rows_of, S,
                                           steps, prec, keep)
            check = train.numbers(traffic, config, hp, got["losses"],
                                  got["first"], got["delta_norms"], ref)
            print(json.dumps({"seed": seed, "variant": name,
                              "correct": check.correct,
                              "readings": check.report()}), flush=True)


def serve_control(config: dict, traffic: dict, seeds, seconds: float,
                  devices) -> None:
    from chipbench import serve

    for seed in seeds:
        found = {}

        def also(params, sampled):
            found["control"] = serve.control_gaps(config, traffic, params,
                                                  sampled)

        result, check, _ = serve.run(config, traffic, seed, seconds, False,
                                     devices, time.perf_counter(),
                                     after=also)
        control = common.Check()
        control.number("logit_gap", max(found["control"]),
                       traffic["limits"]["logit_gap"])
        print(json.dumps({"seed": seed, "correct": check.correct,
                          "readings": check.report(),
                          "control_correct": control.correct,
                          "control_readings": control.report(),
                          "control_per_request": found["control"],
                          "metrics": result["metrics"]}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    spec = common.load_spec()
    w, config, traffic = common.cell(spec, args.workload)
    common.setup_src_path()
    common.enable_compile_cache()
    devices = common.require_chips(w["chips"])
    if traffic["kind"] == "train":
        train_control(config, traffic, args.seeds)
    else:
        serve_control(config, traffic, args.seeds, args.seconds, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
