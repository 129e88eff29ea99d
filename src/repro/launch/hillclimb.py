import os
# 512 fake CPU devices; pinned to the CPU so this never takes an
# attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Perf hillclimbing driver: re-lower a dry-run cell under a variant and
report the roofline-term deltas vs baseline.

    PYTHONPATH=src python -m repro.launch.hillclimb \
        --arch qwen1.5-0.5b --shape train_4k \
        --variant '{"weight_overrides": {"mlp": null, "heads": null}}'

Variants are JSON dicts (see launch/dryrun.py::build_cell).  Results are
appended to results/hillclimb.json with the variant recorded, so the
EXPERIMENTS.md §Perf log can cite exact configurations.
"""
import argparse
import json
import sys

from repro.launch.dryrun import run_cell

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


def terms(rec):
    return dict(
        compute_ms=rec["flops"] / PEAK_FLOPS * 1e3,
        memory_ms=rec["bytes_accessed"] / HBM_BW * 1e3,
        collective_ms=rec["collective_bytes"] / LINK_BW * 1e3,
        temp_gb=rec.get("temp_size_in_bytes", 0) / 1e9,
        arg_gb=rec.get("argument_size_in_bytes", 0) / 1e9,
    )


def fmt(t):
    return (f"compute={t['compute_ms']:.2f}ms memory={t['memory_ms']:.2f}ms "
            f"collective={t['collective_ms']:.2f}ms temp={t['temp_gb']:.2f}GB "
            f"args={t['arg_gb']:.2f}GB")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="{}")
    ap.add_argument("--label", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/hillclimb.json")
    ap.add_argument("--no-baseline", action="store_true")
    args = ap.parse_args(argv)

    variant = json.loads(args.variant)
    rows = []
    if not args.no_baseline:
        base = run_cell(args.arch, args.shape, args.multi_pod, verbose=False)
        base["variant"] = "baseline"
        rows.append(base)
        print(f"baseline : {fmt(terms(base))}")
    rec = run_cell(args.arch, args.shape, args.multi_pod, variant=variant,
                   verbose=False)
    rec["variant"] = args.label or json.dumps(variant, sort_keys=True)
    rows.append(rec)
    t = terms(rec)
    print(f"variant  : {fmt(t)}")
    if rows[0] is not rec and rows[0]["status"] == "ok":
        b = terms(rows[0])
        for k in ("compute_ms", "memory_ms", "collective_ms", "temp_gb"):
            if b[k] > 0:
                print(f"  Δ{k}: {100 * (t[k] / b[k] - 1):+.1f}%")
    prev = []
    if os.path.exists(args.out):
        prev = json.load(open(args.out))
    prev.extend(rows)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(prev, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
