#!/usr/bin/env python3
"""Read the correctness comparison's two readings for a cell, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, in one process: weights from the seed, one window of the
cell's own traffic at its own load, the drain, and the run's sample of
served requests.  Over that sample it decides ``correct`` twice, through the
harness's own ``Harness.check`` and ``passed`` with the configuration's
limits: once for the program (the widest gap by which a served token's
float32 reference logit lies below the reference's best, as ``run.py``
compares it), and once for the control, the same reference computed in fp8
(e4m3, one scale per vector) in the program's place, with the gap of the
token it puts first at each of the same positions.  The program has to come
out correct and the control not: the exit code is 1 where either does not.
Benchmark runs never run the control.
"""
import argparse
import json
import os
import sys
from typing import Dict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as RUN  # noqa: E402


def readings(h: "RUN.Harness", traffic: Dict, seed: int,
             seconds: float) -> Dict:
    """One window from ``seed``; the program's and the control's checks."""
    h.load(seed)
    rec = h.window(traffic, seed, seconds)
    h.free()
    program, control = h.check(rec, seed), h.check(rec, seed, control=True)
    return {"seed": seed, "requests": len(rec["requests"]),
            "completed": sum(r["done"] for r in rec["requests"]),
            "program_correct": RUN.passed(program),
            "control_correct": RUN.passed(control),
            "program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = RUN.cell_of(RUN.load_json(os.path.join(RUN.ROOT, "BENCHMARK.json")),
                       args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    h = RUN.Harness(spec["cfg"])
    ok = True
    for seed in args.seeds:
        r = readings(h, spec["traffic"], seed, args.seconds)
        print(json.dumps(r), flush=True)
        ok = ok and r["program_correct"] and not r["control_correct"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
