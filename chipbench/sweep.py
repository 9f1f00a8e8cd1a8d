#!/usr/bin/env python3
"""Find the knee of a serving cell once: one engine, set up once, serves
the cell's traffic at each given rate for ``--seconds``; for each rate
it prints the requests due, finished and still waiting at the close and
the output tokens per second. The knee is the highest rate at which the
waiting queue does not grow over the window (two or fewer waiting at
the close); ``--set-rate 0.8`` writes 0.8 of it into the traffic file.

    python3 chipbench/sweep.py --workload <serve cell> --seed <n> \\
        --seconds 30 --rates 6 9 12 15

A tool for defining a cell; the benchmark's runs never call it.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import common  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--set-rate", type=float, default=None,
                    help="write this share of the knee into the cell's "
                         "traffic file as its rate")
    args = ap.parse_args()
    spec = common.load_spec()
    w, config, traffic = common.cell(spec, args.workload)
    common.setup_src_path()
    common.enable_compile_cache()
    devices = common.require_chips(w["chips"])
    from chipbench import serve

    vocab = config["vocab_size"]
    _, eng = serve.make_engine(config, args.seed)
    t = time.perf_counter()
    serve.warm_up(eng, traffic, vocab)
    print(f"warm-up {time.perf_counter() - t:.1f} s", flush=True)
    sustained = []
    for rate in args.rates:
        tr = dict(traffic, rate_per_s=rate)
        due, prompts, outs = serve.schedule(tr, args.seed, args.seconds,
                                            vocab)
        reqs = [serve.Req(d, p, o) for d, p, o in zip(due, prompts, outs)]
        t0 = time.perf_counter()
        close = t0 + args.seconds
        out = serve.serve_window(eng, reqs, t0, close)
        half = [r for r in reqs if r.first is None and t0 + r.due < close]
        toks = sum(s["tokens"] for s in out["steps"] if s["end"] <= close)
        ttft = [((r.first if r.first is not None else close)
                 - (t0 + r.due)) * 1e3 for r in reqs if t0 + r.due < close]
        early = [x for r, x in zip(reqs, ttft) if r.due < args.seconds / 2]
        late = [x for r, x in zip(reqs, ttft) if r.due >= args.seconds / 2]
        print(json.dumps({
            "rate": rate, "due": len(ttft),
            "finished": len(out["finished"]),
            "waiting_at_close": eng.queue_depth,
            "unserved_at_close": len(half),
            "tokens_per_s": toks / args.seconds,
            "ttft_p95_first_half_ms": common.percentile(early, 95),
            "ttft_p95_second_half_ms": common.percentile(late, 95),
            "steps": len(out["steps"])}), flush=True)
        if eng.queue_depth <= 2:
            sustained.append(rate)
        for rid in out["open"]:
            eng.evict(rid)
    knee = max(sustained) if sustained else None
    print(json.dumps({"knee": knee, "device": common.device_info(devices)}))
    if args.set_rate and knee:
        path = os.path.join(common.HERE, "workloads",
                            f"{w['traffic']}.json")
        traffic["rate_per_s"] = round(args.set_rate * knee, 3)
        with open(path, "w") as f:
            json.dump(traffic, f, indent=1)
            f.write("\n")
        print(json.dumps({"rate_per_s": traffic["rate_per_s"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
