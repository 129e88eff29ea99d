"""The control comes out not correct: the reference computed in float8,
the step below the configuration's bfloat16, put in the served tokens'
place and judged by the run's own comparison.  At a size the CPU holds,
on the rounds a short run served; the same readings at the cells' own
sizes come from calibrate.py on the chip."""
import pytest

import bench
from bench_small import small_cell


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: None)


@pytest.mark.parametrize("name", ["qwen05b.agentic.offline",
                                  "minicpm2b.agentic.offline"])
def test_control_fails_the_limit_and_the_program_passes(name):
    cell = small_cell(name)
    seed = 2**31 + 1
    out = bench.run(cell, seed, 3.0, False, 0.0, require_tpu=False,
                    control=True)
    limit = out["compared"]["max_logit_gap_sd"]["limit"]
    assert out["correct"] is False
    assert out["compared"]["max_logit_gap_sd"]["value"] > limit
    assert out["program_max_logit_gap_sd"] <= limit
