"""The plain reference against the program's own forward pass, at a
size the CPU holds, for each configuration the benchmark serves."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
from bench_small import small_cell

CELLS = ["qwen05b.agentic.offline", "minicpm2b.agentic.offline"]


@pytest.mark.parametrize("name", CELLS)
def test_weights_match_program_layout(name):
    from repro.models import abstract_params
    cell = small_cell(name)
    ref = bench.reference_module(cell.config)
    params = ref.weights(cell.config, 7)
    want = abstract_params(bench.program_config(cell.config))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    assert got == jax.tree.map(lambda a: (a.shape, str(a.dtype)), want)


@pytest.mark.parametrize("name", CELLS)
def test_reference_matches_program_forward(name):
    """Logits of one causal pass, in units of each row's spread over the
    vocabulary.  The program computes in bfloat16 (its matmuls and its
    residual stream), the reference in float32: 0.1 standard deviations
    is several times the rounding seen here and far below what a wrong
    scale, norm, bias or rotation gives (of order 1)."""
    from repro.models import forward
    cell = small_cell(name)
    config = cell.config
    ref = bench.reference_module(config)
    params = ref.weights(config, 11)
    tokens = np.random.default_rng(0).integers(
        2, config["vocab_size"], size=96).astype(np.int32)
    rows = np.arange(96, dtype=np.int32)
    want = np.asarray(ref.logits(config, params, tokens, rows))
    got = np.asarray(forward(params, bench.program_config(config),
                             jnp.asarray(tokens)[None])[0][0])
    got = got / ref.dims(config).logit_scale
    sd = want.std(-1, keepdims=True)
    assert np.max(np.abs(got - want) / sd) < 0.1


def test_reference_tail_padding_does_not_reach_rows():
    cell = small_cell("qwen05b.agentic.offline")
    config = cell.config
    ref = bench.reference_module(config)
    params = ref.weights(config, 3)
    tokens = np.random.default_rng(1).integers(2, 512, 40).astype(np.int32)
    rows = np.arange(40, dtype=np.int32)
    padded = np.concatenate([tokens, np.full(24, 5, np.int32)])
    a = np.asarray(ref.logits(config, params, tokens, rows))
    b = np.asarray(ref.logits(config, params, padded, rows))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_control_departs_from_reference():
    """The float8 control is a different computation: its logits differ
    from the float32 reference's by a visible share of their spread."""
    cell = small_cell("qwen05b.agentic.offline")
    config = cell.config
    ref = bench.reference_module(config)
    params = ref.weights(config, 5)
    tokens = np.random.default_rng(2).integers(2, 512, 64).astype(np.int32)
    rows = np.arange(64, dtype=np.int32)
    f32 = np.asarray(ref.logits(config, params, tokens, rows))
    fp8 = np.asarray(ref.logits(config, params, tokens, rows, "fp8"))
    assert np.max(np.abs(fp8 - f32) / f32.std(-1, keepdims=True)) > 0.05
