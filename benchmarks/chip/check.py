"""The comparison that decides ``correct``.

After the window, a sample of the rounds that finished is drawn from the
seed: the longest round, the longest whose prompt hit the prefix cache,
then others until some hundreds of served tokens are in it.  The plain
reference (``references/<name>.py``) runs once over each round's prompt
and served tokens, teacher-forced, and gives at each served position the
reference's logits.  The compared number is the widest gap by which a
served token's reference logit lies below the reference's best, in units
of that position's standard deviation of the logits over the vocabulary
(``max_logit_gap_sd``): 0 where the served token is the reference's
argmax, small where bfloat16 rounding broke a near-tie, large where the
served path computed on wrong data.  The limit for each configuration is
in its file under ``correct``, with the readings it was set from.

The control reads, at the same positions, the gap of the token that the
reference computed in float8 puts first.
"""
from __future__ import annotations

from typing import List

import numpy as np

MIN_TOKENS = 384
MAX_ROUNDS = 8


def sample(finished: List[dict], seed: int) -> List[dict]:
    if not finished:
        return []
    size = lambda f: len(f["prompt"]) + len(f["generated"])
    picked = [max(finished, key=size)]
    hits = [f for f in finished if f["cached"] > 0]
    if hits:
        h = max(hits, key=size)
        if h is not picked[0]:
            picked.append(h)
    rest = [f for f in finished if all(f is not p for p in picked)]
    n = sum(len(f["generated"]) for f in picked)
    for i in np.random.default_rng([seed, 4]).permutation(len(rest)):
        if n >= MIN_TOKENS or len(picked) >= MAX_ROUNDS:
            break
        picked.append(rest[i])
        n += len(rest[i]["generated"])
    return picked


def gaps(ref, config: dict, params, rounds: List[dict], max_seq: int,
         rows_pad: int, control: bool = False) -> dict:
    """Per served token, the reference's gap in standard deviations;
    with ``control`` also the gap of the float8 reference's choice."""
    served_gap, control_gap = [], []
    for r in rounds:
        prompt, gen = r["prompt"], r["generated"]
        seq = np.zeros(max_seq, np.int32)
        seq[:len(prompt) + len(gen) - 1] = (prompt + gen)[:-1]
        n = len(gen)
        rows = np.zeros(rows_pad, np.int32)
        rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        lg = np.asarray(ref.logits(config, params, seq, rows))[:n]
        top, sd = lg.max(-1), lg.std(-1)
        idx = np.arange(n)
        served_gap.append((top - lg[idx, gen]) / sd)
        if control:
            lc = np.asarray(ref.logits(config, params, seq, rows,
                                       precision="fp8"))[:n]
            control_gap.append((top - lg[idx, lc.argmax(-1)]) / sd)
    out = {"rounds": len(rounds),
           "hit_rounds": sum(1 for r in rounds if r["cached"] > 0),
           "tokens": int(sum(len(g) for g in served_gap)),
           "max_logit_gap_sd": float(np.max(np.concatenate(served_gap)))
           if served_gap else float("nan")}
    if control:
        out["control_max_logit_gap_sd"] = float(
            np.max(np.concatenate(control_gap)))
    return out
