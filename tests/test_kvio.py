"""KV serialisation: state -> FullBlock bytes -> state roundtrip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.engines import kvio
from repro.models import init_decode_state, init_params
from repro.models.model import append_step

KEY = jax.random.PRNGKey(0)

PAGED_ARCHS = ["qwen1.5-0.5b", "gemma2-2b", "granite-moe-3b-a800m",
               "llama4-maverick-400b-a17b", "ds27b", "llava-next-34b"]


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_serialize_roundtrip(arch):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, KEY)
    b, s, cap = 2, 12, 24
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    st = init_decode_state(cfg, b, cap)
    _, st = append_step(params, cfg, toks, st,
                        jnp.zeros((b,), jnp.int32))
    # serialise slot 0 tokens [0, 12), restore into a fresh state
    kv = kvio.serialize_kv(cfg, st, 0, 0, s)
    assert kv.dtype == np.uint8
    assert kv.shape[0] == kvio.n_attn_layers(cfg)
    assert kv.shape[1] == s
    assert kv.shape[2] == kvio.kv_row_bytes(cfg)
    st2 = init_decode_state(cfg, b, cap)
    st2 = kvio.deserialize_kv(cfg, st2, 0, 0, kv)
    # all attention-cache leaves must agree on slot 0, [0, s)
    def check(a, b_):
        if a.ndim >= 3 and a.shape[-2:] == b_.shape[-2:]:
            pass
    axes = kvio.batch_axes_of_state(cfg)
    kv1 = kvio.serialize_kv(cfg, st2, 0, 0, s)
    np.testing.assert_array_equal(kv, kv1)


def test_serialize_strided_host_layout():
    """A TPU array can reach the host with non-C strides; serialising it
    must give the same bytes as the C-ordered array."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    st = jax.tree.map(
        lambda a: jax.random.normal(KEY, a.shape).astype(a.dtype),
        init_decode_state(cfg, 2, 16))
    strided = jax.tree.map(lambda a: np.asfortranarray(np.asarray(a)), st)
    np.testing.assert_array_equal(kvio.serialize_kv(cfg, strided, 1, 3, 11),
                                  kvio.serialize_kv(cfg, st, 1, 3, 11))


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "zamba2-2.7b", "ds27b"])
def test_slot_get_set_roundtrip(arch):
    cfg = get_config(arch).reduced()
    st = init_decode_state(cfg, 3, 16)
    axes = kvio.batch_axes_of_state(cfg)
    # fill slot 1 with random data, move to slot 2 of a fresh state
    st_r = jax.tree.map(
        lambda a: jax.random.normal(KEY, a.shape).astype(a.dtype), st)
    sub = kvio.slot_get(st_r, axes, 1)
    st2 = kvio.slot_set(st, axes, 2, sub)
    sub2 = kvio.slot_get(st2, axes, 2)
    for a, b in zip(jax.tree.leaves(sub), jax.tree.leaves(sub2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_deserialized_cache_continues_decode():
    """The restored cache is functionally identical: continuing decode
    from deserialised KV matches continuing from the live state."""
    from repro.models import decode_step
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    b, s, cap = 1, 8, 16
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    st = init_decode_state(cfg, b, cap)
    _, st = append_step(params, cfg, toks, st, jnp.zeros((b,), jnp.int32))
    kv = kvio.serialize_kv(cfg, st, 0, 0, s)
    st2 = kvio.deserialize_kv(cfg, init_decode_state(cfg, b, cap), 0, 0, kv)
    nxt = jnp.array([5], jnp.int32)
    lengths = jnp.full((b,), s, jnp.int32)
    l1, _ = decode_step(params, cfg, nxt, st, lengths)
    l2, _ = decode_step(params, cfg, nxt, st2, lengths)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
