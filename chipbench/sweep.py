#!/usr/bin/env python3
"""Find the knee of a serving cell once: one engine, set up once, serves
the cell's traffic at each given rate for ``--seconds``; for each rate
it serves the cell's ``preload_seconds`` first, as a run does, and
prints for the window that follows the requests due and finished, those
waiting at its open and at its close, and the output tokens per second
against those the traffic asks for. Give rates well past the knee: the
most tokens per second that any rate reads is taken as the engine's
saturated throughput. The knee is the highest rate that the engine
holds with room to spare: two or fewer waiting at the close, and under
0.9 of that throughput served. (The queue at the close alone is one
sample: past saturation it can read empty while the p95 wait is
seconds.) ``--set-rate 0.8`` writes 0.8 of the knee into the traffic
file.

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
    preload = traffic["preload_seconds"]
    read = []
    for rate in args.rates:
        tr = dict(traffic, rate_per_s=rate)
        due, prompts, outs = serve.schedule(tr, args.seed,
                                            preload + args.seconds, vocab)
        reqs = [serve.Req(d, p, o) for d, p, o in zip(due, prompts, outs)]
        t0 = time.perf_counter()
        opened, close = t0 + preload, t0 + preload + args.seconds
        waiting = {}
        out = serve.serve_window(
            eng, reqs, t0, close,
            lambda: waiting.setdefault("open", eng.queue_depth), opened)
        due_in = [r for r in reqs if opened <= t0 + r.due < close]
        toks = sum(s["tokens"] for s in out["steps"]
                   if opened < s["end"] <= close)
        ttft = [((r.first if r.first is not None else close)
                 - (t0 + r.due)) * 1e3 for r in due_in]
        print(json.dumps({
            "rate": rate, "due": len(due_in),
            "finished": sum(r.last > opened for r in out["finished"]),
            "waiting_at_open": waiting.get("open"),
            "waiting_at_close": eng.queue_depth,
            "active_at_close": len(out["open"]) - eng.queue_depth,
            "tokens_per_s": toks / args.seconds,
            "asked_tokens_per_s": sum(r.out for r in due_in) / args.seconds,
            "ttft_p95_ms": common.percentile(ttft, 95),
            "steps": sum(opened < s["end"] <= close
                         for s in out["steps"])}), flush=True)
        read.append((rate, eng.queue_depth, toks / args.seconds))
        for rid in out["open"]:
            eng.evict(rid)
    saturated = max(t for _, _, t in read)
    held = [r for r, waiting, t in read
            if waiting <= 2 and t < 0.9 * saturated]
    knee = max(held) if held else None
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
