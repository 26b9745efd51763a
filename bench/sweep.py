#!/usr/bin/env python3
"""Sweep an open-loop cell's offered rate on the chip, to find its knee.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1 1.5 2 3

One set-up, then one window per rate on a freshly reset engine (same
weights, same compiled programs).  A rate replaces the mix's
``arrivals.rate_rps`` (or ``mean_rps``); everything else is the traffic
file's.  Each window prints one JSON line: the rate, requests sent and
completed, TTFT p50/p90, TPOT p90, and the median TTFT of the first and the
last third of the window, whose growth shows a queue that does not drain.
The knee is the highest rate whose p90 TTFT stays near the lowest rates'
and whose late third is no slower than its early third.
"""
import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as RUN  # noqa: E402
from record import percentile  # noqa: E402


def summary(rec, rate):
    reqs, T = rec["requests"], rec["seconds"]
    end = rec["closed_s"]
    ttft = [(r["times"][0] if r["times"] else end) - r["t_s"] for r in reqs]
    third = [[t for t, r in zip(ttft, reqs) if lo <= r["t_s"] < hi]
             for lo, hi in ((0, T / 3), (2 * T / 3, T))]
    tpot = RUN.reader("tpot_p90_ms").read(rec)
    return {"rate": rate, "sent": len(reqs),
            "completed": sum(r["done"] for r in reqs),
            "ttft_p50_s": percentile(ttft, 50), "ttft_p90_s": percentile(ttft, 90),
            "tpot_p90_ms": tpot,
            "ttft_med_first_third_s": percentile(third[0], 50),
            "ttft_med_last_third_s": percentile(third[1], 50),
            "lateness_max_s": max(rec["lateness"]),
            "compiles_in_window": rec["compiles_in_window"],
            "steps_per_dispatch": RUN.reader("steps_per_dispatch").read(rec),
            "prefix_hit_share": RUN.reader("prefix_hit_share").read(rec)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--drain", type=float, default=60.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = RUN.cell_of(RUN.load_json(os.path.join(RUN.ROOT, "BENCHMARK.json")),
                       args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    h = RUN.Harness(spec["cfg"])
    h.load(args.seed)
    for rate in args.rates:
        tr = copy.deepcopy(spec["traffic"])
        arr = tr["arrivals"]
        arr["mean_rps" if "mean_rps" in arr else "rate_rps"] = rate
        rec = h.window(tr, args.seed, args.seconds, drain_s=args.drain)
        rec.update(setup_s=0.0, peaks=None)
        print(json.dumps(summary(rec, rate)), flush=True)
        if any(not r["done"] for r in rec["requests"]):
            break                      # the queue never drained: past the knee
        h.reset()
    return 0


if __name__ == "__main__":
    sys.exit(main())
