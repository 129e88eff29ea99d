"""Kernel micro-benchmarks (interpret mode on CPU — wall times are NOT
TPU times; the TPU-side performance story lives in §Roofline, derived
from the compiled dry-run.  These runs exist to (a) exercise the kernels
at paper-realistic shapes and (b) report the modelled MXU utilisation of
the chosen BlockSpecs)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops

from benchmarks.common import emit


def _bench(fn, *args, iters=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(quick: bool = False):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)

    # prefix-append flash attention at an agentic shape: 429-token append
    # over a 4k prefix (scaled down 8x for interpret-mode runtime)
    b, hq, hkv, dh = 1, 8, 2, 64
    sq, skv = 64, 512
    q = jax.random.normal(ks[0], (b, hq, sq, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, skv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, skv, dh), jnp.float32)
    us = _bench(ops.flash_attention, q, k, v, block_q=32, block_k=128)
    flops = 4 * b * hq * sq * skv * dh
    emit("kernel/flash_attention/append64_prefix512", us,
         f"{flops / 1e6:.1f} MFLOP interpret-mode")

    # paged decode attention
    npool, pt, npages = 64, 16, 16
    g = hq // hkv
    q1 = jax.random.normal(ks[3], (b, hkv, g, dh), jnp.float32)
    kp = jax.random.normal(ks[4], (npool, hkv, pt, dh), jnp.float32)
    vp = jax.random.normal(ks[5], (npool, hkv, pt, dh), jnp.float32)
    tbl = jax.random.randint(ks[6], (b, npages), 0, npool)
    ln = jnp.array([npages * pt - 3], jnp.int32)
    us = _bench(ops.paged_attention, q1, kp, vp, tbl, ln)
    emit("kernel/paged_attention/256tok", us, "decode 1 token vs 256 paged")

    # layer-block gather (layerwise prefill hotspot)
    pool = jax.random.randint(ks[7], (64, 8, 16, 256), 0, 255
                              ).astype(jnp.uint8)
    table = jnp.arange(32, dtype=jnp.int32)
    us = _bench(ops.kv_layer_gather, pool, table, layer=3)
    emit("kernel/kv_layer_gather/32blocks", us,
         f"{32 * 16 * 256 / 1024:.0f} KiB gathered")


if __name__ == "__main__":
    run()
