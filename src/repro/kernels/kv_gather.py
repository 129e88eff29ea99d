"""KV LayerBlock gather — the layerwise-prefill data-movement hotspot.

Layerwise prefill (paper §4.1) streams *one layer's* KV for the whole
prefix into HBM right before that layer's attention.  The prefix lives
in paged FullBlocks ``[layers, page_tokens, kv_feature]``; for layer l
the engine must gather ``pool[table[i], l]`` for every page i of the
sequence into a contiguous ``(n_pages·page_tokens, kv_feature)`` stream
buffer.  A gather like this is exactly the op that fragments into "a
multitude of fine-grained data chunks" (§4.3) — fusing it into one
Pallas kernel with scalar-prefetched page ids turns it into a single
pipelined DMA sweep (block i+1's HBM read overlaps block i's VMEM
write-out), the TPU analogue of the paper's doorbell-batched RDMA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import tpu_params


def _gather_kernel(table_ref, layer_ref, pool_ref, out_ref):
    out_ref[0] = pool_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_layer_gather(pool, table, *, layer, interpret: bool = False):
    """pool (n_pool, layers, pt, feat); table (n,) i32; layer i32 scalar
    -> gathered (n, pt, feat) LayerBlock stream for ``layer``.

    ``layer`` rides in scalar prefetch beside the page table, not as a
    static argument: one compiled kernel then serves every layer of a
    LayerBlock stream."""
    n_pool, n_layers, pt, feat = pool.shape
    n = table.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, 1, pt, feat),
                         lambda i, tbl, lyr: (tbl[i], lyr[0], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, pt, feat),
                               lambda i, tbl, lyr: (i, 0, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, pt, feat), pool.dtype),
        compiler_params=tpu_params("arbitrary"),
        interpret=interpret,
    )(table, jnp.reshape(layer, (1,)).astype(jnp.int32), pool)


def _scatter_kernel(table_ref, layer_ref, stream_ref, pool_in_ref, out_ref):
    del pool_in_ref   # aliased with the output; only written pages change
    out_ref[0, 0] = stream_ref[0]


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def kv_layer_scatter(pool, table, stream, *, layer,
                     interpret: bool = False):
    """Inverse of kv_layer_gather: write LayerBlocks back into FullBlock
    pages (used when persisting the newly-computed append KV).  The pool
    is donated and aliased with the output, so untouched pages persist
    without a copy."""
    n_pool, n_layers, pt, feat = pool.shape
    n = table.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, pt, feat), lambda i, tbl, lyr: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, pt, feat),
                               lambda i, tbl, lyr: (tbl[i], lyr[0], 0, 0)),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        compiler_params=tpu_params("arbitrary"),
        interpret=interpret,
        input_output_aliases={3: 0},
    )(table, jnp.reshape(layer, (1,)).astype(jnp.int32), stream, pool)
