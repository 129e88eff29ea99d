"""Prefix-append flash attention — the paper's prefill compute pattern.

In agentic serving ≥95 % of the prompt hits the KV-Cache: the engine
computes attention for a *short append chunk* of queries over a *long
loaded prefix* plus the chunk itself.  This kernel fuses that pattern:

    q:      (batch, heads, s_q, head_dim)      — append chunk
    k, v:   (batch, kv_heads, s_kv, head_dim)  — prefix ‖ append (concat)
    out:    (batch, heads, s_q, head_dim)

with causal masking at global positions (query row i sits at absolute
position ``kv_len - s_q + i``).  TPU mapping: grid is
(batch, kv_heads, q_blocks, kv_blocks) with the kv dimension innermost
("arbitrary" semantics) carrying the online-softmax state in VMEM
scratch; every matmul is shaped (block_q·group, block_k) /
(block_k, head_dim) to land on the MXU with 128-aligned dims.

VMEM budget at the default 128/512 blocking, head_dim 128, group ≤ 8:
q 256 KB + k,v 256 KB + acc(f32) 512 KB + m/l ≈ 1.1 MB — comfortably
double-bufferable in 16 MB VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def tpu_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  q_start: int, n_kv_blocks: int, kv_len: int,
                  softcap: float, window: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                    # (g, block_q, dh)
    k = k_ref[0, 0]                    # (block_k, dh)
    v = v_ref[0, 0]
    g, bq, dh = q.shape

    q2 = q.reshape(g * bq, dh)
    s = jax.lax.dot_general(
        q2, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # (g*bq, block_k)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    # flattened (g, bq) row index: gi*bq + r -> global q position uses r only
    rows = q_start + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (g * bq, block_k), 0) % bq
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (g * bq, block_k), 1)
    mask = cols < kv_len
    if causal:
        mask &= cols <= rows
    if window > 0:
        mask &= (rows - cols) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (g*bq,)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (g*bq, dh)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _fin():
        lse = l_ref[...]
        lse = jnp.where(lse == 0.0, 1.0, lse)
        out = (acc_ref[...] / lse[:, None]).astype(o_ref.dtype)
        o_ref[0, 0] = out.reshape(g, bq, dh)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "softcap", "window", "block_q", "block_k",
                     "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, softcap: float = 0.0,
                    window: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """q (b,hq,sq,dh); k,v (b,hkv,skv,dh) — append queries over
    prefix‖append keys.  Returns (b,hq,sq,dh)."""
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(dh)

    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sq_p, skv_p = sq + pad_q, skv + pad_k
    nq, nk = sq_p // block_q, skv_p // block_k
    qg = q.reshape(b, hkv, g, sq_p, dh)

    q_start = skv - sq      # global position of the first query row

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, q_start=q_start, n_kv_blocks=nk, kv_len=skv,
        softcap=softcap, window=window)

    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, block_q, dh),
                         lambda b_, h, qi, ki: (b_, h, 0, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h, qi, ki: (b_, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h, qi, ki: (b_, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, block_q, dh),
                               lambda b_, h, qi, ki: (b_, h, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, sq_p, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * block_q,), jnp.float32),
            pltpu.VMEM((g * block_q,), jnp.float32),
            pltpu.VMEM((g * block_q, dh), jnp.float32),
        ],
        compiler_params=tpu_params("parallel", "parallel", "parallel",
                                   "arbitrary"),
        interpret=interpret,
    )(qg, k, v)
    out = out.reshape(b, hq, sq_p, dh)
    return out[:, :, :sq]
