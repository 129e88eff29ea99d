"""Readings for a configuration's ``correct`` limit, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,... --seconds <s> [--control-seeds 11,12,13]

For each seed: one run of the cell (a short window at the cell's own
load), its sampled rounds compared with the plain reference.  On the
``--control-seeds`` the run is a control run: the float8 reference's
choices at the same positions are compared in the served tokens' place,
through the same comparison, so ``correct`` has to come out false; the
program's own gap is read in the same run.  The lower reading is the
largest ``max_logit_gap_sd`` of the program over the seeds; the upper
reading is the smallest of the control.  Prints one JSON line per seed, then
both readings.  Needs a TPU, as run.py does.
"""
import time

T_START = time.perf_counter()

import argparse                                     # noqa: E402
import json                                         # noqa: E402
import os                                           # noqa: E402
import sys                                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import bench                                        # noqa: E402


def readings(cell, seeds, control_seeds, seconds, require_tpu=True,
             t_start=None):
    program, control = [], []
    for seed in seeds:
        t0 = time.perf_counter() if t_start is None else t_start
        t_start = None
        out = bench.run(cell, seed, seconds, False, t0,
                        require_tpu=require_tpu,
                        control=seed in control_seeds)
        gap = out["compared"]["max_logit_gap_sd"]["value"]
        line = {"seed": seed, "correct": out["correct"],
                "tokens": out["compared"]["tokens_compared"]["value"]}
        if seed in control_seeds:
            control.append(gap)
            gap = out["program_max_logit_gap_sd"]
            line["control_max_logit_gap_sd"] = control[-1]
        program.append(gap)
        line["max_logit_gap_sd"] = gap
        print(json.dumps(line), flush=True)
    return program, control


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    try:
        program, control = readings(bench.load_cell(args.workload), seeds,
                                    ctrl, args.seconds, t_start=T_START)
    except bench.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"lower": max(program),
                      "upper": min(control) if control else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
