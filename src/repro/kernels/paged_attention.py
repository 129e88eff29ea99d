"""Paged decode attention.

Decode engines keep KV in paged blocks (the same FullBlock token
granularity the storage layer uses), addressed by a per-sequence block
table.  One new token per sequence attends over its pages:

    q:           (batch, kv_heads, group, head_dim)
    k/v_pool:    (n_pages, kv_heads, page_tokens, head_dim)
    block_table: (batch, max_pages) int32     — page ids per sequence
    lengths:     (batch,) int32               — valid tokens per sequence

TPU mapping: grid (batch, kv_heads, n_pages) with the page dimension
innermost carrying online-softmax state; the block table and lengths
ride in scalar-prefetch so each page's BlockSpec index_map can pick the
right pool row (``table[b, i]``) while the DMA for page i+1 overlaps the
compute on page i — the HBM→VMEM streaming analogue of the paper's
layerwise loading.  Heads sit before tokens in the pool so that one
(head, page) block is a whole ``(page_tokens, head_dim)`` tile: the TPU
lowering accepts a block's last two dims only when each is a multiple
of (8, 128) or spans its array dim.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF, tpu_params


def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, page_tokens: int,
                  n_pages: int, softcap: float):
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                       # (g, dh)
    k = k_ref[0, 0]                       # (page_tokens, dh)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (g, pt)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    pos = pi * page_tokens + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(pos < len_ref[b], s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=1)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = m_new

    @pl.when(pi == n_pages - 1)
    def _fin():
        lse = l_ref[...]
        lse = jnp.where(lse == 0.0, 1.0, lse)
        o_ref[0, 0] = (acc_ref[...] / lse[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("softcap", "interpret"))
def paged_attention(q, k_pool, v_pool, block_table, lengths, *,
                    softcap: float = 0.0, interpret: bool = False):
    """q (b, hkv, g, dh); pools (n_pages, hkv, pt, dh);
    block_table (b, max_pages) i32; lengths (b,) i32 -> (b, hkv, g, dh)."""
    b, hkv, g, dh = q.shape
    n_pool, _, pt, _ = k_pool.shape
    max_pages = block_table.shape[1]
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(
        _paged_kernel, scale=scale, page_tokens=pt, n_pages=max_pages,
        softcap=softcap)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda b_, h, pi, tbl, ln: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, pt, dh),
                         lambda b_, h, pi, tbl, ln: (tbl[b_, pi], h, 0, 0)),
            pl.BlockSpec((1, 1, pt, dh),
                         lambda b_, h, pi, tbl, ln: (tbl[b_, pi], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda b_, h, pi, tbl, ln: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        compiler_params=tpu_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(block_table, lengths, q, k_pool, v_pool)
    return out
