"""Ahead-of-time compiles of the Pallas kernels and the served steps for
a v5e chip.

Nothing here runs on a chip: each kernel is lowered and compiled by the
TPU compiler for a *described* v5e device at qwen1.5-0.5b widths (16 kv
heads, head_dim 64, 24 layers, bf16 KV, 16-token FullBlock pages), so a
BlockSpec or VMEM budget the chip would refuse fails here even though
the interpret-mode tests pass.  The decode step is compiled at the chip
benchmark's size, where the compiler's own layouts decide whether the
cache is updated in place; admit's slot write, the install's per-layer
placement and the prefill step at minicpm-2b's published widths and its
cell's serving size (4 slots of 2048 tokens), where one copy of a state
more than the chip needs would not fit beside 5.45 GB of weights.

The topology is described inside a module fixture, never while a module
is imported: only one process may hold the TPU library, and every
pytest-xdist worker imports this file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.engines import kvio, runtime
from repro.engines.kvio import kv_row_bytes, n_attn_layers
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kv_gather import kv_layer_gather, kv_layer_scatter
from repro.kernels.paged_attention import paged_attention
from repro.models import init_decode_state, init_params

PAGE_TOKENS = 16          # chip_smoke.py's FullBlock size


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def qwen():
    return get_config("qwen1.5-0.5b")


@pytest.fixture(scope="module")
def minicpm():
    return get_config("minicpm-2b")


MINICPM_SLOTS, MINICPM_SEQ = 4, 2048     # configs/minicpm-2b.json serve


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["gather", "scatter"])
def test_kv_layer_gather_scatter_compile(one_chip, qwen, op):
    n_pool, n = 64, 48
    pool = _sds((n_pool, n_attn_layers(qwen), PAGE_TOKENS,
                 kv_row_bytes(qwen)), jnp.uint8, one_chip)
    table = _sds((n,), jnp.int32, one_chip)
    layer = _sds((), jnp.int32, one_chip)
    if op == "gather":
        lowered = kv_layer_gather.lower(pool, table, layer=layer)
    else:
        stream = _sds((n, PAGE_TOKENS, kv_row_bytes(qwen)), jnp.uint8,
                      one_chip)
        lowered = kv_layer_scatter.lower(pool, table, stream, layer=layer)
    _assert_kernel(lowered.compile())


@pytest.mark.parametrize("s_app,s_kv", [(256, 1024), (40, 296)],
                         ids=["aligned", "unaligned"])
def test_flash_attention_compile(one_chip, qwen, s_app, s_kv):
    hq, hkv, dh = qwen.n_heads, qwen.n_kv_heads, qwen.head_dim
    q = _sds((1, hq, s_app, dh), jnp.bfloat16, one_chip)
    k = _sds((1, hkv, s_kv, dh), jnp.bfloat16, one_chip)
    _assert_kernel(flash_attention.lower(q, k, k).compile())


def test_paged_attention_compile(one_chip, qwen):
    b, max_pages, n_pool = 8, 1024 // PAGE_TOKENS, 512
    hkv, dh = qwen.n_kv_heads, qwen.head_dim
    q = _sds((b, hkv, qwen.n_heads // hkv, dh), jnp.bfloat16, one_chip)
    pool = _sds((n_pool, hkv, PAGE_TOKENS, dh), jnp.bfloat16, one_chip)
    table = _sds((b, max_pages), jnp.int32, one_chip)
    lengths = _sds((b,), jnp.int32, one_chip)
    _assert_kernel(paged_attention.lower(q, pool, pool, table,
                                         lengths).compile())


def test_decode_step_updates_cache_in_place(one_chip, qwen):
    """8 slots of 4096 tokens: the compiled step aliases the whole cache
    to its output, copies no array a layer slice large or larger (no
    relayout of the cache, no layer slice copied out), and needs no
    scratch near a layer slice's size."""
    b, s = 8, 4096
    shape = lambda a: _sds(a.shape, a.dtype, one_chip)
    params = jax.tree.map(shape, jax.eval_shape(
        lambda: init_params(qwen, jax.random.PRNGKey(0))))
    state = jax.tree.map(shape, init_decode_state(qwen, b, s,
                                                  abstract=True))
    ids = _sds((b,), jnp.int32, one_chip)
    compiled = runtime._decode_step.lower(params, qwen, ids, state,
                                          ids).compile()
    kv_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                   for a in jax.tree.leaves(state))
    layer_elems = b * s * qwen.n_kv_heads * qwen.head_dim
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == kv_bytes
    assert mem.temp_size_in_bytes < 2 * layer_elems // 8
    copied = [math.prod(int(d) for d in dims.split(","))
              for dims in re.findall(r"= bf16\[([\d,]+)\]\S* copy\(",
                                     compiled.as_text())]
    assert not [n for n in copied if n >= layer_elems]


def _state(cfg, b, sharding):
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding),
                        init_decode_state(cfg, b, MINICPM_SEQ,
                                          abstract=True))


def _nbytes(tree):
    return sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))


def _slot_layer(cfg):
    """Elements of one layer of one slot's K (or V)."""
    return MINICPM_SEQ * cfg.n_kv_heads * cfg.head_dim


def _largest_copy(compiled):
    return max([math.prod(int(d) for d in dims.split(","))
                for dims in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(",
                                       compiled.as_text())], default=0)


def test_admit_writes_decode_state_in_place(one_chip, minicpm):
    """The slot write aliases the whole decode state (3.02 GB) to its
    output and needs no second state, nor a copy of the one written."""
    state = _state(minicpm, MINICPM_SLOTS, one_chip)
    sub = _state(minicpm, 1, one_chip)
    axes = tuple(jax.tree.leaves(kvio.batch_axes_of_state(minicpm)))
    compiled = kvio._put_slot.lower(state, axes, _sds((), jnp.int32,
                                                      one_chip),
                                    sub).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _nbytes(state) == 3019898880
    assert mem.temp_size_in_bytes < _nbytes(sub) // 100
    assert _largest_copy(compiled) < _slot_layer(minicpm)


def test_install_places_layer_in_place(one_chip, minicpm):
    """One layer's 1024 hit rows go into the one-slot state (0.755 GB)
    in place: the state aliases its output, with no copy of it."""
    sub = _state(minicpm, 1, one_chip)["kv"]
    rows = _sds((1024, minicpm.n_kv_heads, minicpm.head_dim),
                jnp.bfloat16, one_chip)
    at = (_sds((), jnp.int32, one_chip),) * 3
    compiled = kvio._place_rows.lower(sub, at, {"k": rows,
                                              "v": rows}).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _nbytes(sub) == 754974720
    assert mem.temp_size_in_bytes < _nbytes(sub) // 100
    assert _largest_copy(compiled) < _slot_layer(minicpm)


@pytest.mark.parametrize("tokens", [64, 2048])
def test_prefill_step_in_place_one_position(one_chip, minicpm, tokens):
    """The prefill step aliases the request's state to its output, copies
    no layer of it, and returns one position's logits, not one per
    token (1.0 GB in float32 at 2048)."""
    shape = lambda a: _sds(a.shape, a.dtype, one_chip)
    params = jax.tree.map(shape, jax.eval_shape(
        lambda: init_params(minicpm, jax.random.PRNGKey(0))))
    sub = _state(minicpm, 1, one_chip)
    compiled = runtime._append_step.lower(
        params, minicpm, _sds((1, tokens), jnp.int32, one_chip), sub,
        _sds((1,), jnp.int32, one_chip)).compile()
    logits = compiled.out_info[0]
    assert logits.shape == (1, 1, minicpm.vocab_size)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _nbytes(sub)
    assert mem.output_size_in_bytes < _nbytes(sub) + 4 * (
        minicpm.vocab_size + 1024)
    assert _largest_copy(compiled) < _slot_layer(minicpm)
