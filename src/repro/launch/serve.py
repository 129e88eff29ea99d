"""Serving launcher: run the DualPath serving system on an arch.

Serves the config at its published widths; ``--reduced`` swaps in the
smoke-scale config for CPU runs.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --agents 4 --mode dualpath
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve --reduced
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving import ServingSystem
from repro.sim.traces import Round, Trajectory

BLOCK_TOKENS = 16


def serve(cfg, params, trajectories, *, mode="dualpath", n_pe=1, n_de=1,
          max_seq=256, de_slots=4, split_reads=False):
    """Serve ``trajectories`` offline through one ServingSystem.

    Returns ``(system, sessions)``: ``system.stats()`` holds the byte and
    token accounting, each session's ``context`` its full token stream.
    """
    system = ServingSystem(cfg, params, n_pe=n_pe, n_de=n_de, mode=mode,
                           block_tokens=BLOCK_TOKENS, max_seq=max_seq,
                           de_slots=de_slots, split_reads=split_reads)
    return system, system.run_offline(trajectories)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--mode", choices=("dualpath", "basic"),
                    default="dualpath")
    ap.add_argument("--pe", type=int, default=1)
    ap.add_argument("--de", type=int, default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    trajs = [Trajectory(i, [Round(20, 4)] * args.rounds)
             for i in range(args.agents)]
    system, sessions = serve(cfg, params, trajs, mode=args.mode,
                             n_pe=args.pe, n_de=args.de,
                             de_slots=max(4, args.agents))
    print(f"completed {sum(s.rounds_done for s in sessions)} rounds "
          f"across {len(sessions)} agents ({args.mode})")
    for k, v in system.stats().items():
        print(f"  {k}: {v:,}" if isinstance(v, int) else f"  {k}: {v}")


if __name__ == "__main__":
    main()
