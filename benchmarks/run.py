"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` shrinks agent
counts (CI-sized); default sizes reproduce the paper's operating points
(fig7 at 1024 agents reaches the ~1.87x headline).

``--smoke-all`` runs every benchmark that declares a ``--smoke`` mode
(a ``smoke`` parameter on its ``run()``) and fails on the first
acceptance violation — the single CI entry point, so new figures are
covered by registering here instead of editing the workflow.  Smoke
runs return their headline metrics; ``benchmarks/perf_gate.py`` turns
those into the committed ``BENCH_*.json`` trajectory.
"""
import argparse
import inspect
import os
import sys
import traceback

if __package__ in (None, ""):       # direct `python benchmarks/run.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def suite():
    from benchmarks import (fig7_offline, fig8_pd_ratio, fig9_append_gen,
                            fig10_online, fig12_ablation, fig13_balance,
                            fig_bottleneck, fig_elastic, fig_fleet,
                            fig_interference, fig_online_serving,
                            fig_resilience, fig_slo, fig_tiered_prefetch,
                            kernel_bench, micro_submit, microbench_sim,
                            roofline, table1_cache_compute, table3_scale)
    return {
        "table1": table1_cache_compute.run,
        "micro_submit": micro_submit.run,
        "kernels": kernel_bench.run,
        "fig7": fig7_offline.run,
        "fig8": fig8_pd_ratio.run,
        "fig9": fig9_append_gen.run,
        "fig10": fig10_online.run,
        "fig12": fig12_ablation.run,
        "fig13": fig13_balance.run,
        "fig_tiered": fig_tiered_prefetch.run,
        "fig_online_serving": fig_online_serving.run,
        "fig_slo": fig_slo.run,
        "fig_interference": fig_interference.run,
        "fig_elastic": fig_elastic.run,
        "fig_resilience": fig_resilience.run,
        "fig_bottleneck": fig_bottleneck.run,
        "microbench_sim": microbench_sim.run,
        "fig_fleet": fig_fleet.run,
        "table3": table3_scale.run,
        "roofline": roofline.run,
    }


def smoke_benchmarks(full=None):
    """The registered benchmarks that declare a smoke mode."""
    full = full or suite()
    return {name: fn for name, fn in full.items()
            if "smoke" in inspect.signature(fn).parameters}


def run_smoke_all(only=None) -> dict:
    """Run every smoke-capable benchmark (optionally filtered to the
    ``only`` name set); returns ``{name: metrics}`` with each smoke
    run's headline-metric dict (empty when a benchmark returns none).
    Raises on the first acceptance violation or an unknown name."""
    from benchmarks.common import header
    header()
    smokes = smoke_benchmarks()
    if only:
        unknown = set(only) - set(smokes)
        if unknown:
            raise SystemExit(f"--only names without a --smoke mode: "
                             f"{sorted(unknown)}")
        smokes = {n: fn for n, fn in smokes.items() if n in only}
    out = {}
    # Mark the shared-process suite run: wall-clock-gated benchmarks
    # (fig_fleet's >=50x assert) apply their hard thresholds only when
    # run in isolation — a long-lived suite process carries heap
    # fragmentation from earlier benchmarks that skews short timed
    # legs.  The metrics are still collected and band-gated by the
    # perf trajectory, suite-run against suite-run baselines.
    os.environ["REPRO_BENCH_SUITE"] = "1"
    try:
        for name, fn in smokes.items():
            metrics = fn(smoke=True)
            out[name] = dict(metrics or {})
            print(f"{name} smoke: PASS", file=sys.stderr)
            try:    # drop compiled programs between benchmarks: a long
                import jax  # single-process run OOMs the CPU LLVM JIT
                jax.clear_caches()  # (same guard as tests/conftest.py)
            except ImportError:
                pass
            import gc
            gc.collect()
    finally:
        os.environ.pop("REPRO_BENCH_SUITE", None)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--list", action="store_true",
                    help="list benchmark names and exit")
    ap.add_argument("--smoke-all", action="store_true",
                    help="run every benchmark that declares --smoke and "
                         "fail on the first acceptance violation")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="with --smoke-all: write the collected headline "
                         "metrics as perf_gate-schema JSON to PATH")
    args = ap.parse_args(argv)
    if args.json and not args.smoke_all:
        raise SystemExit("--json requires --smoke-all")

    from benchmarks.common import header

    full = suite()
    if args.list:
        smokes = smoke_benchmarks(full)
        for name, fn in full.items():
            doc = (sys.modules[fn.__module__].__doc__ or
                   "").strip().splitlines()
            tag = " [smoke]" if name in smokes else ""
            print(f"{name}{tag}: {doc[0] if doc else ''}")
        return
    only = set(args.only.split(",")) if args.only else None
    if args.smoke_all:
        metrics = run_smoke_all(only=only)
        if args.json:
            import json
            from benchmarks.perf_gate import SCHEMA
            with open(args.json, "w") as f:
                json.dump({"schema": SCHEMA, "metrics": metrics}, f,
                          indent=2, sort_keys=True)
                f.write("\n")
            print(f"wrote {args.json}", file=sys.stderr)
        return
    header()
    failed = []
    for name, fn in full.items():
        if only and name not in only:
            continue
        kw = ({"quick": args.quick}
              if "quick" in inspect.signature(fn).parameters else {})
        try:
            fn(**kw)
        except Exception as e:  # noqa: BLE001 — report, run the rest
            traceback.print_exc()
            print(f"{name},0.0,ERROR:{e!r}", file=sys.stderr)
            failed.append(name)
    if failed:
        raise SystemExit(f"benchmarks raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
