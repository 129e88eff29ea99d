"""From what a run recorded to numbers: window arithmetic over the
rounds, and the reduction of a profiler trace to device metrics.

Rounds carry host-clock stamps in seconds from the window's start:
``due`` (its session's arrival, or the end of its think gap), ``first``
(first token ready, -1 if never), ``done`` (last token, -1 if never)
and ``gen`` (tokens asked for).  Percentiles are nearest-rank, as
``repro.obs.metrics.Histogram.percentile`` computes them.

A trace is reduced from a compact form: ``{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, dur_ns], ...]}]}]}``, which
:func:`compact_xplane` makes from the profiler's ``.xplane.pb`` and
which a test keeps a small recorded example of.
"""
from __future__ import annotations

import bisect
import math
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile, ``q`` in [0, 100]; NaN when empty."""
    if not xs:
        return float("nan")
    s = sorted(xs)
    return s[max(1, math.ceil(q / 100.0 * len(s))) - 1]


def ttfts(rounds: Iterable[dict], close_s: float) -> Tuple[List[float], int]:
    """Time to first token of every round due in the window, from when
    it was due; and how many of those never produced a first token."""
    out, missing = [], 0
    for r in rounds:
        if r["due"] > close_s:
            continue
        if r["first"] < 0:
            missing += 1
        else:
            out.append(r["first"] - r["due"])
    return out, missing


def tpots(rounds: Iterable[dict], close_s: float) -> List[float]:
    """Each round finished in the window: its mean gap between output
    tokens, first token to last."""
    return [(r["done"] - r["first"]) / (r["gen"] - 1) for r in rounds
            if 0 <= r["done"] <= close_s and r["first"] >= 0
            and r["gen"] > 1]


def tokens_in_window(emitted: Iterable[Tuple[float, int]],
                     close_s: float) -> int:
    """Output tokens emitted at a host time within [0, close_s]."""
    return sum(n for t, n in emitted if 0 <= t <= close_s)


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def compact_xplane(path: str) -> dict:
    """The profiler's xplane file as plain planes, lines and events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _device_ops(trace: dict) -> List[List[list]]:
    """Per device plane, its op events."""
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith(DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out.append(line["events"])
    return out


def host_events(trace: dict, names: Sequence[str]) -> List[list]:
    """Host-side events (spans) whose name is one of ``names``."""
    want = set(names)
    return [ev for plane in trace["planes"]
            if not plane["name"].startswith(DEVICE_PREFIX)
            for line in plane["lines"] for ev in line["events"]
            if ev[0] in want]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def window_of(trace: dict, marker: str) -> Optional[Tuple[int, int]]:
    """The host span named ``marker``: the traced window, in ns."""
    evs = host_events(trace, [marker])
    if not evs:
        return None
    name, t0, dur = max(evs, key=lambda e: e[2])
    return t0, t0 + dur


def device_busy(trace: dict, window: Tuple[int, int]) -> Optional[dict]:
    """Busy seconds per device inside ``window`` (the union of its op
    intervals), averaged over the devices, and the idle intervals of
    the first device."""
    lo, hi = window
    per_dev = []
    idle = None
    for events in _device_ops(trace):
        busy = _union(_clip([(s, s + d) for _, s, d in events], lo, hi))
        per_dev.append(sum(b - a for a, b in busy))
        if idle is None:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            idle = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    if not per_dev:
        return None
    return {"busy_s": sum(per_dev) / len(per_dev) / 1e9,
            "window_s": (hi - lo) / 1e9, "idle": idle}


def op_seconds(trace: dict, window: Tuple[int, int],
               match: str) -> Tuple[float, int]:
    """Summed device seconds and count of the ops whose name contains
    ``match``, inside ``window``; first device."""
    lo, hi = window
    total, n = 0, 0
    for events in _device_ops(trace)[:1]:
        for name, s, d in events:
            if match in name and s >= lo and s + d <= hi:
                total += d
                n += 1
    return total / 1e9, n


def top_ops(trace: dict, window: Tuple[int, int],
            k: int = 10) -> List[list]:
    """The device ops that took most time in the window, by name."""
    lo, hi = window
    tot: Dict[str, int] = defaultdict(int)
    for events in _device_ops(trace)[:1]:
        for name, s, d in events:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                tot[name] += b - a
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_by_host(trace: dict, idle: List[Tuple[int, int]],
                 span_names: Sequence[str], other: str = "orchestration",
                 k: int = 10) -> List[list]:
    """Idle device time attributed to what the host was doing.  Spans
    may nest (a persist inside a decode step): ``span_names`` is in
    order of priority, and a stretch of idle time goes to the first
    name whose span covers it; what no span covers goes to ``other``."""
    tot: Dict[str, int] = defaultdict(int)
    left = list(idle)
    for name in span_names:
        spans = _union([(s, s + d) for _, s, d in host_events(trace, [name])])
        starts = [s for s, _ in spans]
        rest = []
        for a, b in left:
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            cur = a
            while i < len(spans) and spans[i][0] < b:
                s, e = spans[i]
                if e > cur:
                    if s > cur:
                        rest.append((cur, s))
                    lo, hi = max(s, cur), min(e, b)
                    tot[name] += hi - lo
                    cur = hi
                i += 1
            if cur < b:
                rest.append((cur, b))
        left = rest
    tot[other] += sum(b - a for a, b in left)
    ranked = sorted(((n, v) for n, v in tot.items() if v > 0),
                    key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]
