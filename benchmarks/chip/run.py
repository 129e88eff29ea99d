"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Prints what it measured on standard error, the numbers compared for
``correct`` last there, and as the last line of standard output one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``compared``.
Exits 2, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.
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
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    try:
        out = bench.run(cell, args.seed, args.seconds, bool(args.trace),
                        T_START)
    except bench.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
