"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,sq,skv,dh,bq,bk",
    [
        (1, 4, 4, 64, 64, 64, 32, 32),       # MHA square
        (2, 8, 2, 32, 256, 64, 32, 64),      # GQA append (short q, long kv)
        (1, 8, 1, 17, 130, 32, 16, 64),      # ragged (padding paths)
        (2, 4, 4, 128, 128, 128, 128, 128),  # MXU-aligned
        (1, 16, 8, 8, 512, 64, 8, 256),      # deep prefix
    ])
def test_flash_attention_sweep(dtype, b, hq, hkv, sq, skv, dh, bq, bk):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (b, hq, sq, dh), dtype)
    k = rand(ks[1], (b, hkv, skv, dh), dtype)
    v = rand(ks[2], (b, hkv, skv, dh), dtype)
    out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk)
    ref = ops.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOLS[dtype], rtol=TOLS[dtype])


@pytest.mark.parametrize("softcap,window", [(30.0, 0), (0.0, 64), (50.0, 48)])
def test_flash_attention_softcap_window(softcap, window):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (1, 4, 96, 64), jnp.float32)
    k = rand(ks[1], (1, 2, 160, 64), jnp.float32)
    v = rand(ks[2], (1, 2, 160, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, softcap=softcap, window=window,
                              block_q=32, block_k=32)
    ref = ops.flash_attention_ref(q, k, v, softcap=softcap, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_flash_attention_noncausal():
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (1, 4, 64, 32), jnp.float32)
    k = rand(ks[1], (1, 4, 64, 32), jnp.float32)
    v = rand(ks[2], (1, 4, 64, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    ref = ops.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hkv,g,dh,npool,pt,npages",
    [
        (2, 4, 2, 64, 16, 16, 6),
        (1, 1, 8, 128, 8, 32, 4),
        (3, 2, 1, 32, 32, 8, 10),
    ])
def test_paged_attention_sweep(dtype, b, hkv, g, dh, npool, pt, npages):
    ks = jax.random.split(KEY, 5)
    q = rand(ks[0], (b, hkv, g, dh), dtype)
    kp = rand(ks[1], (npool, hkv, pt, dh), dtype)
    vp = rand(ks[2], (npool, hkv, pt, dh), dtype)
    tbl = jax.random.randint(ks[3], (b, npages), 0, npool)
    lengths = jax.random.randint(ks[4], (b,), 1, npages * pt)
    out = ops.paged_attention(q, kp, vp, tbl, lengths)
    ref = ops.paged_attention_ref(q, kp, vp, tbl, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOLS[dtype], rtol=TOLS[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.uint8])
@pytest.mark.parametrize("npool,nl,pt,feat,n", [(8, 4, 16, 32, 5),
                                                (16, 2, 8, 128, 16)])
def test_kv_gather_scatter_sweep(dtype, npool, nl, pt, feat, n):
    ks = jax.random.split(KEY, 3)
    if dtype == jnp.uint8:
        pool = jax.random.randint(ks[0], (npool, nl, pt, feat), 0, 255
                                  ).astype(jnp.uint8)
        stream = jax.random.randint(ks[1], (n, pt, feat), 0, 255
                                    ).astype(jnp.uint8)
    else:
        pool = rand(ks[0], (npool, nl, pt, feat), dtype)
        stream = rand(ks[1], (n, pt, feat), dtype)
    tbl = jax.random.choice(ks[2], npool, (n,), replace=False)
    for layer in (0, nl - 1):
        g = ops.kv_layer_gather(pool, tbl, layer=layer)
        gr = ops.kv_layer_gather_ref(pool, tbl, layer=layer)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(gr))
        s = ops.kv_layer_scatter(pool.copy(), tbl, stream, layer=layer)
        sr = ops.kv_layer_scatter_ref(pool, tbl, stream, layer=layer)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


def test_flash_matches_model_attention():
    """Kernel agrees with the model-layer chunked attention path."""
    from repro.models.layers import attend
    ks = jax.random.split(KEY, 3)
    b, hq, hkv, sq, skv, dh = 2, 8, 4, 64, 192, 64
    q = rand(ks[0], (b, hq, sq, dh), jnp.float32)
    k = rand(ks[1], (b, hkv, skv, dh), jnp.float32)
    v = rand(ks[2], (b, hkv, skv, dh), jnp.float32)
    out = ops.flash_attention(q, k, v, block_q=32, block_k=64)
    # model layout is (b, s, h, dh)
    ref = attend(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                 v.transpose(0, 2, 1, 3), causal=True,
                 q_offset=skv - sq, force_dense=False)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.transpose(0, 2, 1, 3)),
                               atol=3e-5)
