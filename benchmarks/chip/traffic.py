"""One general traffic generator, driven by a mix's JSON parameters.

A mix file (``traffic/<mix>.json``) holds only numbers: size
distributions, the sizes a run may use, the arrival process and its
rate.  This module turns them into plain data: per session a list of
``(append, gen, think_s)`` rounds and an arrival time.  It imports
nothing of the program under test, so a change to the program cannot
change the yardstick.

The multi-round generator is a copy of the Table-2 generator of the
DualPath paper's agent traces (``generate_trajectory``: per-trajectory
"chattiness" ``u`` with appends scaled by ``1/u^2``, lognormal appends
and gens, a larger first round, truncation at the context cap), with
two changes:

* every size is snapped to the nearest of a fixed list of sizes in the
  mix file (``sizes``).  The program compiles one prefill program per
  distinct prompt length and one small program per distinct hit and
  persist length; a fixed list of sizes keeps that set the same for
  every seed, so set-up can compile all of it and nothing compiles in
  the measured window;
* the work is drawn once from the mix's own ``work_seed``, and a run's
  ``--seed`` reorders it: the order of the sessions, and the tokens.
  Every seed then offers the same sizes at the same arrival times, so
  two seeds differ by order alone.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import List, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass
class Session:
    key: int                                   # token seed of the session
    rounds: List[Tuple[int, int, float]]       # (append, gen, think_s)


@dataclass
class Work:
    sessions: List[Session]
    arrivals: List[float]                      # seconds after window start
    max_outstanding: int | None                # client-side cap, or None
    drain_s: float                             # wait for first tokens


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def snap(x: float, sizes: List[int]) -> int:
    """The size nearest to ``x`` on a log scale."""
    lx = math.log(max(x, 1.0))
    return min(sizes, key=lambda s: abs(math.log(s) - lx))


def _lognormal(rng, mean, sigma):
    mu = math.log(mean) - sigma * sigma / 2.0
    return float(rng.lognormal(mu, sigma))


def agent_session(rng, p: dict, max_len: int) -> List[Tuple[int, int, float]]:
    """One multi-round agent trajectory (the Table-2 generator)."""
    sizes = p["sizes"]
    first_sizes = [s for s in sizes["first"] if s <= max_len // 4]
    u = float(rng.lognormal(0.0, p["chattiness_sigma"]))
    app_mean = max(p["append_floor"], p["append_mean"] / (u * u))
    stop_total = int(min(max_len, p["total_frac"] * max_len
                         * rng.uniform(0.75, 1.25)))
    first = float(np.clip(_lognormal(rng, p["append_mean"] * p["first_mult"],
                                     p["first_sigma"]),
                          p["first_min"], max_len // 4))
    g0 = max(1.0, _lognormal(rng, p["gen_mean"], p["gen_sigma"]))
    rounds = [(snap(first, first_sizes), snap(g0, sizes["gen"]), 0.0)]
    total = rounds[0][0] + rounds[0][1]
    while total < stop_total:
        a = snap(max(1.0, _lognormal(rng, app_mean, p["append_sigma"])),
                 sizes["append"])
        g = snap(max(1.0, _lognormal(rng, p["gen_mean"], p["gen_sigma"])),
                 sizes["gen"])
        if total + a + g > max_len:
            break
        th = _lognormal(rng, p["think_mean_s"], p["think_sigma"]) \
            if p["think_mean_s"] > 0 else 0.0
        rounds.append((a, g, th))
        total += a + g
    return rounds


def single_request(rng, p: dict, max_len: int) -> List[Tuple[int, int, float]]:
    """One single-round request with an unshared prompt."""
    sizes = p["sizes"]
    prompt = float(np.clip(_lognormal(rng, p["prompt_mean"],
                                      p["prompt_sigma"]),
                           p["prompt_min"], p["prompt_max"]))
    g = max(1.0, _lognormal(rng, p["gen_mean"], p["gen_sigma"]))
    a, g = snap(prompt, sizes["prompt"]), snap(g, sizes["gen"])
    if a + g > max_len:
        raise ValueError(f"request {a}+{g} exceeds the context cap {max_len}")
    return [(a, g, 0.0)]


KINDS = {"sessions": agent_session, "single": single_request}


def generate(p: dict, max_len: int, seed: int, seconds: float) -> Work:
    """The run's work: the mix's fixed set of sessions and arrival times,
    drawn from ``work_seed``, the sessions in an order drawn from
    ``seed``.  An open-loop mix holds exactly the sessions that arrive
    within ``seconds``, so every seed offers the same sizes at the same
    times; a backlog holds its stated count, all due at the start, in
    the same order for every seed."""
    make = KINDS[p["kind"]]
    arr = p["arrival"]
    if arr["process"] == "poisson":
        gaps = np.random.default_rng([p["work_seed"], 1]).exponential(
            1.0 / arr["per_s"], size=int(arr["per_s"] * seconds * 3) + 16)
        # the first session arrives at the start of the window
        times = np.concatenate([[0.0], np.cumsum(gaps)])
        times = times[times < seconds]
    elif arr["process"] == "backlog":
        times = np.zeros(int(arr["sessions"]))
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    base = np.random.default_rng(p["work_seed"])
    rounds = [make(base, p, max_len) for _ in range(len(times))]
    # a backlog is served from its head, and a window reaches only its
    # first few dozen rounds: it keeps one order, so every seed serves
    # the same sizes
    order = np.arange(len(times)) if arr["process"] == "backlog" else \
        np.random.default_rng([seed, 1]).permutation(len(times))
    key_base = int(np.random.default_rng([seed, 3]).integers(1 << 30))
    sessions = [Session(key_base + i, rounds[j]) for i, j in enumerate(order)]
    return Work(sessions, [float(t) for t in times],
                arr.get("max_outstanding"), float(p.get("drain_s", 0.0)))


def summary(sessions: List[Session]) -> dict:
    """Table-2 style means over a set of sessions."""
    turns = [len(s.rounds) for s in sessions]
    appends = [r[0] for s in sessions for r in s.rounds]
    gens = [r[1] for s in sessions for r in s.rounds]
    thinks = [r[2] for s in sessions for r in s.rounds[1:]]
    ctx = [sum(a + g for a, g, _ in s.rounds[:i])
           for s in sessions for i in range(len(s.rounds))]
    return dict(turns=float(np.mean(turns)), append=float(np.mean(appends)),
                gen=float(np.mean(gens)), context=float(np.mean(ctx)),
                think=float(np.mean(thinks)) if thinks else 0.0)
