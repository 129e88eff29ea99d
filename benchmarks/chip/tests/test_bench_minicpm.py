"""minicpm-2b as BENCHMARK.json runs it: its reference draws the embedding
at 0.02 / scale_emb, so the model's output follows its context and the
comparison that decides ``correct`` can fail."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
import bench_small

CELL = "minicpm2b.agentic.offline"


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: None)


@pytest.fixture
def cell(monkeypatch):
    """The listed cell at the small size, with its own configuration file
    rather than the pending entry's."""
    monkeypatch.delitem(bench_small.PENDING, CELL, raising=False)
    return bench_small.small_cell(CELL)


def _dense():
    return bench.reference_module({"reference": "dense_decoder"})


def test_cell_runs_the_reference_that_can_fail(cell):
    assert cell.config["reference"] == "minicpm_decoder"
    assert cell.config["reduced"] == []
    assert cell.config["scale_emb"] == 12


def test_weights_are_dense_decoders_but_the_embedding(cell):
    config = cell.config
    ref = bench.reference_module(config)
    got, dense = ref.weights(config, 2**33 + 7), _dense().weights(config,
                                                                 2**33 + 7)
    tok = np.asarray(got["embed"]["tok"], np.float32)
    want = np.asarray(dense["embed"]["tok"], np.float32) / config["scale_emb"]
    np.testing.assert_allclose(tok, want, rtol=2 ** -7, atol=0)
    assert abs(tok.std() / (0.02 / config["scale_emb"]) - 1) < 0.05
    rest = lambda p: {k: v for k, v in p.items() if k != "embed"}
    jax.tree.map(np.testing.assert_array_equal, rest(got), rest(dense))


def test_reference_matches_program_forward(cell):
    from repro.models import forward
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


@pytest.mark.parametrize("reference,echoes", [("dense_decoder", True),
                                              ("minicpm_decoder", False)])
def test_top_token_is_the_input_only_at_std_002(reference, echoes):
    """At the published width, depth and vocabulary cut, the embedding
    drawn at 0.02 makes the top token the input token at every position,
    and the float8 control picks the same; drawn at 0.02 / scale_emb
    neither holds, and the control departs by more than the limit."""
    config = json.loads((bench.HERE / "configs"
                         / "minicpm-2b.mup-init.json").read_text())
    config.update(num_hidden_layers=2, vocab_size=4096)
    ref = bench.reference_module(dict(config, reference=reference))
    params = ref.weights(config, 2**31 + 201)
    n = 128
    tokens = np.random.default_rng(3).integers(
        2, config["vocab_size"], n).astype(np.int32)
    rows = np.arange(n, dtype=np.int32)
    lg = np.asarray(ref.logits(config, params, tokens, rows))
    l8 = np.asarray(ref.logits(config, params, tokens, rows, "fp8"))
    echo = float(np.mean(lg.argmax(-1) == tokens))
    gap = (lg.max(-1) - lg[rows, l8.argmax(-1)]) / lg.std(-1)
    limit = config["correct"]["max_logit_gap_sd"]["limit"]
    if echoes:
        assert echo == 1.0 and gap.max() == 0.0
    else:
        assert echo < 0.05 and gap.max() > limit


def test_control_fails_the_limit_and_the_program_passes(cell):
    # a window long enough that rounds finish on a CPU shared with other
    # test processes
    out = bench.run(cell, 2**31 + 1, 10.0, False, 0.0, require_tpu=False,
                    control=True)
    limit = out["compared"]["max_logit_gap_sd"]["limit"]
    assert out["correct"] is False
    assert out["compared"]["max_logit_gap_sd"]["value"] > limit
    assert out["program_max_logit_gap_sd"] <= limit
