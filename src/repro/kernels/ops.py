"""Jit'd public wrappers for the Pallas kernels.

On CPU the kernels execute in Pallas **interpret mode** — the kernel
body runs in Python with the exact same blocking/masking logic the TPU
lowering uses.  On TPU they compile through Mosaic.  The choice is
automatic from the default backend, overridable per call; any other
backend is an error rather than a silent fall back to the interpreter.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.kv_gather import kv_layer_gather as _gather
from repro.kernels.kv_gather import kv_layer_scatter as _scatter
from repro.kernels.paged_attention import paged_attention as _paged


def _interpret_default() -> bool:
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas lowering for backend {backend!r}")


def flash_attention(q, k, v, *, causal=True, softcap=0.0, window=0,
                    block_q=None, block_k=None, interpret=None):
    kw = {}
    if block_q is not None:
        kw["block_q"] = block_q
    if block_k is not None:
        kw["block_k"] = block_k
    return _flash(q, k, v, causal=causal, softcap=softcap, window=window,
                  interpret=_interpret_default() if interpret is None
                  else interpret, **kw)


def paged_attention(q, k_pool, v_pool, block_table, lengths, *,
                    softcap=0.0, interpret=None):
    return _paged(q, k_pool, v_pool, block_table, lengths, softcap=softcap,
                  interpret=_interpret_default() if interpret is None
                  else interpret)


def kv_layer_gather(pool, table, *, layer, interpret=None):
    return _gather(pool, table, layer=layer,
                   interpret=_interpret_default() if interpret is None
                   else interpret)


def kv_layer_scatter(pool, table, stream, *, layer, interpret=None):
    return _scatter(pool, table, stream, layer=layer,
                    interpret=_interpret_default() if interpret is None
                    else interpret)


# re-export oracles for convenience in tests/benchmarks
flash_attention_ref = ref.flash_attention_ref
paged_attention_ref = ref.paged_attention_ref
kv_layer_gather_ref = ref.kv_layer_gather_ref
kv_layer_scatter_ref = ref.kv_layer_scatter_ref
