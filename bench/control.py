"""The readings the ``correct`` limit of a cell is set from, on the chip.

  python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, one run of the cell as the benchmark makes it (the timed
path at the cell's own load and sizes, in one process) whose check also
runs the control: the reference computed with float8 e4m3 operands, one
precision step below the served bfloat16, judged under the same limits.
Prints one JSON line per seed with whether the program and the control
pass, the program's mean gap and the control's (and the widest gaps
beside them), then the largest program reading and the smallest control
reading of the mean gap.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import check
import run
import spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    devices = run.chips(spec.cell(bench, args.workload)["chips"])
    run.use_compile_cache()
    program, control = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        run.T_START = time.perf_counter()
        r = run.execute(bench, args.workload, seed, args.seconds, False,
                        devices, control=True)
        c = r["check"]
        program.append(c["mean_gap"]["value"])
        control.append(r["control_check"]["mean_gap"]["value"])
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "control_correct": check.passes(r["control_check"]),
                          **{k: v["value"] for k, v in c.items()},
                          **r["readings"], "metrics": r["metrics"]}),
              flush=True)
    print(json.dumps({"program_max": max(program),
                      "control_min": min(control)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
