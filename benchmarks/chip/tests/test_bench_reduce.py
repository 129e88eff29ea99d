"""The trace -> metrics reduction, on a hand-made trace with known
answers (host spans that nest, a window marker), and on a recorded
stretch of a TPU v5e trace, whose plane and line names reduce.py has to
find and whose busy time a second count confirms."""
import json
from pathlib import Path

import numpy as np
import pytest

import reduce


def _trace(ops, host):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_step", 0, 100]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": host}]}]}


HAND = _trace(
    ops=[["fusion", 0, 10], ["kv_layer_gather", 5, 10], ["fusion", 30, 10],
         ["copy", 95, 20]],
    host=[["bench_trace_window", 0, 100], ["decode", 10, 30],
          ["persist", 20, 5], ["install", 60, 10], ["other", 0, 100]])


def test_window_busy_and_idle():
    win = reduce.window_of(HAND, "bench_trace_window")
    assert win == (0, 100)
    busy = reduce.device_busy(HAND, win)
    # ops cover [0,15) [30,40) [95,100) inside the window
    assert busy["busy_s"] == pytest.approx(30e-9)
    assert busy["window_s"] == pytest.approx(100e-9)
    assert busy["idle"] == [(15, 30), (40, 95)]


def test_kernel_time_and_top_ops():
    win = (0, 100)
    assert reduce.op_seconds(HAND, win, "kv_layer_gather") == \
        (pytest.approx(10e-9), 1)
    top = reduce.top_ops(HAND, win)
    assert top[0] == ["fusion", pytest.approx(20e-9)]
    assert [n for n, _ in top] == ["fusion", "kv_layer_gather", "copy"]


def test_idle_goes_to_the_innermost_named_span_first():
    busy = reduce.device_busy(HAND, (0, 100))
    got = dict(reduce.idle_by_host(HAND, busy["idle"],
                                   ["persist", "install", "decode"]))
    # idle [15,30): persist 20-25, decode 15-20 and 25-30
    # idle [40,95): install 60-70, nothing else named
    assert got == {"persist": pytest.approx(5e-9),
                   "decode": pytest.approx(10e-9),
                   "install": pytest.approx(10e-9),
                   "orchestration": pytest.approx(45e-9)}
    assert sum(got.values()) == pytest.approx(70e-9)


def test_no_device_plane_gives_nothing():
    host_only = {"planes": [HAND["planes"][1]]}
    assert reduce.device_busy(host_only, (0, 100)) is None


# --- a recorded stretch of a real v5e trace -------------------------------

RECORDED = json.loads((Path(__file__).resolve().parents[1] / "testdata"
                       / "v5e_short_stretch.json").read_text())


def _busy_on_a_grid(events, lo, hi, step=10):
    """Busy ns by marking a grid of ``step`` ns: a second way to the
    union of the op intervals."""
    grid = np.zeros((hi - lo) // step + 1, bool)
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[(a - lo) // step:(b - lo) // step] = True
    return int(grid.sum()) * step


def test_recorded_trace_planes_are_found():
    ops = reduce._device_ops(RECORDED)
    assert len(ops) == 1 and len(ops[0]) > 100
    assert reduce.host_events(RECORDED, ["decode", "persist"])


def test_recorded_busy_matches_a_second_count():
    lo, hi = RECORDED["window"]
    busy = reduce.device_busy(RECORDED, (lo, hi))
    ops = RECORDED["planes"][0]["lines"][0]["events"]
    want = _busy_on_a_grid(ops, lo, hi)
    assert busy["busy_s"] * 1e9 == pytest.approx(want, abs=20 * len(ops))
    assert busy["window_s"] == pytest.approx(0.04)
    idle = sum(b - a for a, b in busy["idle"])
    assert idle + busy["busy_s"] * 1e9 == pytest.approx(hi - lo)
    # the stretch holds the host's persist between decode steps, and the
    # device waits through most of it
    assert 0.0 < busy["busy_s"] < busy["window_s"]


def test_recorded_idle_attribution_adds_up():
    lo, hi = RECORDED["window"]
    busy = reduce.device_busy(RECORDED, (lo, hi))
    got = dict(reduce.idle_by_host(RECORDED, busy["idle"],
                                   ["persist", "decode"]))
    assert sum(got.values()) * 1e9 == pytest.approx(
        sum(b - a for a, b in busy["idle"]))
    assert got["persist"] > 0
