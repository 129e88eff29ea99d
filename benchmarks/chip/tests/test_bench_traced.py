"""A traced run on the CPU at a small size, the chip check skipped: its
result line holds every per-layer metric of the cell that spans and
counters give, and its trace spans the whole window, so every KV gather
that the window made falls inside it (a backlog's window holds few, and
a stretch of it can hold none)."""
import json
import time

import jax
import pytest

import bench
from bench_small import small_cell
from peaks import PEAKS

SECONDS = 3.0


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(bench, "device_info", lambda chips: jax.devices())
    monkeypatch.setattr(bench, "peaks", lambda kind: PEAKS["TPU v5 lite"])
    runs = []
    real = bench.Run

    def spy(**kw):
        runs.append(real(**kw))
        return runs[-1]
    monkeypatch.setattr(bench, "Run", spy)
    cell = small_cell("qwen05b.agentic.offline")
    out = bench.run(cell, 2**31 + 11, SECONDS, True, time.perf_counter())
    return cell, out, runs[0].rec


def test_traced_run_reports_span_and_counter_metrics(traced):
    cell, out, _ = traced
    assert out["correct"], out["compared"]
    want = {m["name"] for m in cell.per_layer
            if m["source"] in ("program_span", "program_counter")}
    assert want <= set(out["metrics"])
    assert "install_ms_per_ktok" in want
    json.dumps(out)


def test_trace_spans_every_gather_of_the_window(traced):
    _, _, rec = traced
    assert rec.trace is not None and rec.trace_window is not None
    lo, hi = rec.trace_span_perf
    assert hi - lo >= SECONDS
    assert rec.gather_calls
    assert all(lo <= t <= hi for t, _ in rec.gather_calls)
