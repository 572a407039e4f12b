"""Readings for the limits of ``correct``, several seeds in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 [--control]

For each seed: one run of the cell (a timed window at the cell's own
size and load), the numbers ``bench/check.py`` compares, and with
``--control`` the same numbers for the control (the reference at float8
matmul operands in the program's place), the control's verdict under
the cell's limits, and ``lam_err`` of the reference's price update with
each planted fault of ``check.FAULTS``.  Prints one JSON line per seed.
The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from bench import build, check
    from bench.run import devices, execute

    cell = build.workload(args.workload)
    devs = devices(int(cell["chips"]))
    cfg = build.load("configs", cell["config"])
    traffic = build.load("traffic", cell["traffic"])
    lim = check.limits(cell["name"])
    for seed in (int(s) for s in args.seeds.split(",")):
        got = {}

        def both(run, seed):
            if args.control:
                t = time.perf_counter()
                got["control"] = check.control(run, seed)
                got["control_s"] = time.perf_counter() - t
            t = time.perf_counter()
            v = check.run(run, seed)
            got["check_s"] = time.perf_counter() - t
            return v

        t = time.perf_counter()
        out, lines = execute(cell, cfg, traffic, devs, seed=seed,
                             seconds=args.seconds, trace=False,
                             check_fn=both)
        row = {"seed": seed, "correct": out["correct"],
               "program": {k: v["value"] for k, v in out["checks"].items()},
               "readings": [x for x in lines if x.startswith("reading")],
               "metrics": out["metrics"], "check_s": got["check_s"],
               "wall_s": time.perf_counter() - t}
        if args.control:
            ctl = dict(got["control"])
            faults = ctl.pop("faults")
            row["control"] = ctl
            row["control_correct"] = check.verdict(ctl, lim).correct
            row["faults"] = faults
            row["control_s"] = got["control_s"]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
