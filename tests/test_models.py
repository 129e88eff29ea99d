"""Model zoo: per-arch smoke tests + decode/append consistency oracles.

Smoke (assignment requirement): every assigned architecture instantiates
a REDUCED same-family config and runs one forward + one train step on
CPU asserting output shapes and no NaNs.

Oracles: token-by-token decode and chunked append must reproduce the
full-sequence forward.  Exact (bitwise) for non-MoE archs; MoE archs get
a tolerance because chunk-shape-dependent matmul accumulation (1-ulp in
bf16) can flip top-k routing — the known chunked-prefill/MoE
non-reproducibility (documented in DESIGN.md).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, EXTRA_ARCH_IDS, get_config
from repro.models import (count_active_params_analytic,
                          count_params_analytic, decode_step, forward,
                          init_decode_state, init_params)
from repro.models.model import append_step
from repro.training import make_train_step

KEY = jax.random.PRNGKey(0)
ALL_ARCHS = list(ARCH_IDS) + list(EXTRA_ARCH_IDS)
MOE_ARCHS = {"granite-moe-3b-a800m", "llama4-maverick-400b-a17b", "ds27b"}


def _inputs(cfg, b, s, key):
    if cfg.frontend_embed_dim:
        return jax.random.normal(key, (b, s, cfg.frontend_embed_dim),
                                 jnp.float32)
    return jax.random.randint(key, (b, s), 0, cfg.vocab_size)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, KEY)
    b, s = 2, 16
    x = _inputs(cfg, b, s, KEY)
    logits, _ = forward(params, cfg, x)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any()), f"{arch}: NaN in forward"

    opt_init, train_step = make_train_step(cfg, n_microbatches=1)
    opt = opt_init(params)
    if cfg.frontend_embed_dim:
        batch = {"inputs": x,
                 "labels": jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)}
    else:
        batch = {"tokens": jax.random.randint(KEY, (b, s + 1), 0,
                                              cfg.vocab_size)}
    new_params, new_opt, loss = train_step(params, opt, batch)
    assert jnp.isfinite(loss), f"{arch}: non-finite loss"
    # params actually changed
    moved = jax.tree.leaves(jax.tree.map(
        lambda a, b_: bool(jnp.any(a != b_)), params, new_params))
    assert any(moved), f"{arch}: train step did not update params"


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    if not cfg.supports_decode:
        pytest.skip("encoder-only")
    params = init_params(cfg, KEY)
    b, s = 2, 10
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    full, _ = forward(params, cfg, toks)
    st = init_decode_state(cfg, b, 2 * s)
    errs = []
    for i in range(s):
        lg, st = decode_step(params, cfg, toks[:, i], st,
                             jnp.full((b,), i, jnp.int32))
        errs.append(float(jnp.max(jnp.abs(lg - full[:, i]))))
    tol = 0.25 if arch in MOE_ARCHS or cfg.attn_variant == "mla" else 0.0
    assert max(errs) <= tol, f"{arch}: decode-vs-forward err {max(errs)}"


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_append_matches_forward(arch):
    cfg = get_config(arch).reduced()
    if not cfg.supports_decode:
        pytest.skip("encoder-only")
    params = init_params(cfg, KEY)
    b, s = 2, 12
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    full, _ = forward(params, cfg, toks)
    st = init_decode_state(cfg, b, 2 * s)
    errs, off = [], 0
    for chunk in (5, 4, 3):
        lg, st = append_step(params, cfg, toks[:, off:off + chunk], st,
                             jnp.full((b,), off, jnp.int32))
        errs.append(float(jnp.max(jnp.abs(lg - full[:, off:off + chunk]))))
        off += chunk
    tol = 0.25 if arch in MOE_ARCHS else 0.0
    assert max(errs) <= tol, f"{arch}: append-vs-forward err {max(errs)}"


def test_encoder_bidirectional():
    """hubert: flipping a late token changes early logits (no causality)."""
    cfg = get_config("hubert-xlarge").reduced()
    params = init_params(cfg, KEY)
    x = jax.random.normal(KEY, (1, 8, cfg.frontend_embed_dim), jnp.float32)
    l1, _ = forward(params, cfg, x)
    x2 = x.at[:, -1].add(1.0)
    l2, _ = forward(params, cfg, x2)
    assert bool(jnp.any(jnp.abs(l1[:, 0] - l2[:, 0]) > 0)), \
        "encoder is unexpectedly causal"


def test_causal_lm_is_causal():
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    l1, _ = forward(params, cfg, toks)
    toks2 = toks.at[:, -1].set((toks[:, -1] + 1) % cfg.vocab_size)
    l2, _ = forward(params, cfg, toks2)
    np.testing.assert_array_equal(np.asarray(l1[:, :-1]),
                                  np.asarray(l2[:, :-1]))


def test_gemma2_local_global_differ():
    """Local layers mask beyond the window — perturbing a distant token
    must still reach the output through global layers only."""
    cfg = get_config("gemma2-2b").reduced()
    assert cfg.local_window > 0 and cfg.local_global_period == 2
    params = init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (1, 16), 0, cfg.vocab_size)
    logits, _ = forward(params, cfg, toks)
    assert not bool(jnp.isnan(logits).any())


def test_param_counts_match_designations():
    expected = {
        "llava-next-34b": (34e9, 0.10),
        "llama4-maverick-400b-a17b": (400e9, 0.05),
        "granite-moe-3b-a800m": (3e9, 0.15),
        "qwen1.5-0.5b": (0.5e9, 0.15),
        "minicpm-2b": (2.7e9, 0.15),
        "gemma2-2b": (2.6e9, 0.15),
        "nemotron-4-15b": (15e9, 0.10),
        "mamba2-1.3b": (1.3e9, 0.10),
        "hubert-xlarge": (0.96e9, 0.10),
        "zamba2-2.7b": (2.7e9, 0.20),
        "ds27b": (27e9, 0.10),
    }
    for name, (n, tol) in expected.items():
        got = count_params_analytic(get_config(name))
        assert abs(got - n) / n < tol, (name, got / 1e9)


def test_minicpm_registry_matches_benchmark_config():
    """The registry's minicpm-2b is the model the chip benchmark's
    configuration file states, on every key the benchmark carries over
    (``bench.program_config``), so the CPU tests and ``launch/serve.py``
    run it with its muP scalings."""
    import json
    import math
    from pathlib import Path
    conf = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip" / "configs" / "minicpm-2b.json").read_text())
    cfg = get_config("minicpm-2b")
    layers = conf["num_hidden_layers"]
    heads = conf["num_attention_heads"]
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (
        conf["hidden_size"], layers, heads, conf["num_key_value_heads"],
        conf["hidden_size"] // heads, conf["intermediate_size"],
        conf["vocab_size"])
    assert cfg.rms_norm_eps == conf["rms_norm_eps"]
    assert cfg.rope_theta == conf["rope_theta"]
    assert cfg.tie_embeddings == conf["tie_word_embeddings"]
    assert cfg.qkv_bias == conf["qkv_bias"]
    assert cfg.embed_scale == conf["scale_emb"]
    assert math.isclose(cfg.ffn_mult, conf["scale_depth"] / math.sqrt(layers))


def test_active_params():
    a = count_active_params_analytic(get_config("llama4-maverick-400b-a17b"))
    assert 10e9 < a < 20e9          # a17b
    g = count_active_params_analytic(get_config("granite-moe-3b-a800m"))
    assert 0.5e9 < g < 1.1e9        # a800m


def test_moe_ep_matches_ragged_without_drops():
    from repro.models.moe import moe_ffn
    cfg = get_config("granite-moe-3b-a800m").reduced()
    params = init_params(cfg, KEY)
    p = jax.tree.map(lambda a: a[0], params["super_blocks"]["moe"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.d_model),
                          jnp.float32)
    y1 = moe_ffn(p, cfg, x, impl="ragged")
    y2 = moe_ffn(p, cfg, x, impl="ep", capacity_factor=1000.0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


def test_moe_ep_capacity_drops_tokens():
    from repro.models.moe import moe_ep, route
    cfg = get_config("granite-moe-3b-a800m").reduced()
    params = init_params(cfg, KEY)
    p = jax.tree.map(lambda a: a[0], params["super_blocks"]["moe"])["moe"]
    x = jax.random.normal(KEY, (64, cfg.d_model), jnp.float32)
    y_tight = moe_ep(p, cfg, x, capacity_factor=0.1)
    y_loose = moe_ep(p, cfg, x, capacity_factor=1000.0)
    assert bool(jnp.any(jnp.abs(y_tight - y_loose) > 1e-6))
