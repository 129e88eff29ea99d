"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, softcap=0.0, window=0):
    """q (b,hq,sq,dh); k,v (b,hkv,skv,dh)."""
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    qg = q.reshape(b, hkv, g, sq, dh)
    s = jnp.einsum("bngqd,bnkd->bngqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(dh)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    rows = (skv - sq) + jnp.arange(sq)
    cols = jnp.arange(skv)
    ok = jnp.ones((sq, skv), bool)
    if causal:
        ok &= cols[None, :] <= rows[:, None]
    if window > 0:
        ok &= (rows[:, None] - cols[None, :]) < window
    s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngqk,bnkd->bngqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, dh).astype(q.dtype)


def paged_attention_ref(q, k_pool, v_pool, block_table, lengths, *,
                        softcap=0.0):
    """q (b,hkv,g,dh); pools (n,hkv,pt,dh); table (b,np); lengths (b,)."""
    b, hkv, g, dh = q.shape
    n_pool, _, pt, _ = k_pool.shape
    np_ = block_table.shape[1]
    # materialise per-sequence KV: (b, hkv, np*pt, dh)
    k = k_pool[block_table].transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, np_ * pt, dh)
    v = v_pool[block_table].transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, np_ * pt, dh)
    s = jnp.einsum("bngd,bnkd->bngk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(dh)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    valid = jnp.arange(np_ * pt)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngk,bnkd->bngd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def kv_layer_gather_ref(pool, table, *, layer: int):
    return pool[table, layer]


def kv_layer_scatter_ref(pool, table, stream, *, layer: int):
    return pool.at[table, layer].set(stream)
