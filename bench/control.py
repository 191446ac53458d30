#!/usr/bin/env python3
"""Readings that set the limits of `correct`, for one cell, on the chip.

  python3 bench/control.py --workload trcd.sweep --seeds 1,2,3 --seconds 5

For each seed, one process opens the cell as ``run.py`` does, runs a
short window of whole calls at the cell's own size and load, and
compares the points of one call drawn from the seed twice: with the
plain reference (the lower reading: what sound runs give) and with the
control, the reference with one guarantee of the configuration dropped
(its ``control``; the upper reading: it has to come out as not
correct). ``run.py`` never runs the control.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")]

from bench import run  # noqa: E402
from bench.lib import registry  # noqa: E402


def readings(reg, workload: str, seed: int, seconds: float,
             require_tpu: bool = True, cache: bool = True) -> dict:
    opened = run.open_cell(reg, workload, seed, require_tpu, cache)
    if opened is None:
        raise SystemExit(2)
    call, driver, _ = opened
    calls, _, _, failed = run.run_window(call, driver, seconds)
    broken = reg.config(reg.workload(workload)["config"])["control"]
    lower, compared, chosen = run.compare(driver, calls, seed)
    upper, _, _ = run.compare(driver, calls, seed, broken)
    return {"workload": workload, "seed": seed, "call": chosen,
            "compared_points": compared, "failed_points": failed,
            "mismatched_points": lower, "control": broken,
            "control_mismatched_points": upper}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    reg = registry.Registry()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(reg, args.workload, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
