"""Find a cell's knee: its open-loop rate swept in one process.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 0.5,1,2,...

Runs the cell's window once per arrival rate (sessions or requests per
second, in place of the mix file's ``per_s``) and prints per rate the
rounds due and finished, TTFT median, p90 and max from when each round
was due, and the p90 gap between tokens.  The knee is the highest rate
whose TTFT does not grow through the window.  Needs a TPU.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    t0 = T_START
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic["arrival"]["per_s"] = rate
        try:
            out = bench.run(cell, args.seed, args.seconds, False, t0)
        except bench.NoChip as e:
            print(f"sweep.py: {e}", file=sys.stderr)
            return 2
        t0 = time.perf_counter()
        m = {k: v["value"] for k, v in out["metrics"].items()}
        print(json.dumps({"rate": rate, "attempted": out["attempted"],
                          "failed": out["failed"], **m, **out["window"],
                          "correct": out["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
