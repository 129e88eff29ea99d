"""KV state ↔ FullBlock byte serialisation + slot utilities.

The engines keep decode state as padded jnp buffers (layer-leading, for
lax.scan); persistent storage holds FullBlocks ``[layers, tokens, bytes]``
(paper §A.5).  This module converts between them, per attention family:

* gqa (dense/vlm/moe): row = k ‖ v            (2·hkv·dh·dtype bytes/token)
* mla:                 row = c_kv ‖ k_rope    ((r+rd)·dtype bytes/token)

SSM/hybrid archs have no per-token KV; their recurrent state is carried
as an opaque *state blob* snapshot (see engines/runtime.py) — the
transfer paths are identical, only the payload differs.

:func:`layer_stream` is the engine-side realisation of layerwise
loading (paper §4.1): it delivers one attention layer's KV at a time,
gathered through the kernels/kv_gather.py Pallas path, with the next
layer's gather already submitted (in flight on the TrafficManager)
while the current layer is being installed — double buffering at
LayerBlock granularity.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.traffic import TrafficClass, TrafficManager
from repro.models.model import init_decode_state


def batch_axes_of_state(cfg: ModelConfig):
    """Tree matching the decode state with each leaf's batch-axis index
    (stacking puts layers in front, so the axis varies per leaf)."""
    s3 = init_decode_state(cfg, 3, 8, abstract=True)
    s4 = init_decode_state(cfg, 4, 8, abstract=True)

    def find(a, b):
        for i, (x, y) in enumerate(zip(a.shape, b.shape)):
            if x != y:
                return i
        raise AssertionError((a.shape, b.shape))

    return jax.tree.map(find, s3, s4)


def slot_get(state, axes, slot: int):
    """Extract one sequence's state (batch size 1 view)."""
    return jax.tree.map(
        lambda a, ax: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=ax),
        state, axes)


def slot_set(state, axes, slot: int, sub):
    return jax.tree.map(
        lambda a, ax, s: jax.lax.dynamic_update_slice_in_dim(a, s, slot, ax),
        state, axes, sub)


@functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
def _put_slot(state, axes: tuple, slot, sub):
    leaves, tree = jax.tree.flatten(state)
    return jax.tree.unflatten(tree, [
        jax.lax.dynamic_update_slice_in_dim(a, s.astype(a.dtype), slot, ax)
        for a, ax, s in zip(leaves, axes, jax.tree.leaves(sub))])


def put_slot(state, axes, slot: int, sub):
    """:func:`slot_set` that takes ``state`` over: one program per state
    shape (``slot`` is traced) writes ``sub`` into it in place, and the
    buffers passed in are deleted."""
    return _put_slot(state, tuple(jax.tree.leaves(axes)), slot, sub)


# ---------------------------------------------------------------------------
# attention-layer enumeration (canonical layer order for serialisation)
# ---------------------------------------------------------------------------


def _kv_rows(cfg: ModelConfig) -> List[Tuple[str, tuple]]:
    """(state_key, stack_index) per attention layer, in layer order."""
    fam = cfg.family
    rows: List[Tuple[str, tuple]] = []
    if fam in ("dense", "vlm"):
        for li in range(cfg.n_layers):
            rows.append(("kv", (li,)))
    elif fam == "moe":
        m = cfg.moe
        for li in range(m.first_k_dense):
            rows.append(("dense", (li,)))
        n_super = (cfg.n_layers - m.first_k_dense) // m.period
        for i in range(n_super):
            if m.period > 1:
                for j in range(m.period - 1):
                    rows.append(("pre", (i, j)))
            rows.append(("moe", (i,)))
    elif fam == "hybrid":
        n_super = cfg.n_layers // cfg.hybrid_period
        for i in range(n_super):
            rows.append(("shared", (i,)))
    else:
        raise ValueError(fam)
    return rows


def kv_row_bytes(cfg: ModelConfig, dtype_bytes: int = 2) -> int:
    if cfg.attn_variant == "mla":
        return (cfg.mla.kv_lora_rank + cfg.mla.rope_head_dim) * dtype_bytes
    return 2 * cfg.n_kv_heads * cfg.head_dim * dtype_bytes


def n_attn_layers(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.hybrid_period
    return cfg.n_layers


def _to_bytes(a) -> np.ndarray:
    # a TPU array can come to the host in a strided layout, and a dtype
    # view needs C order
    return np.ascontiguousarray(a).reshape(a.shape[0], -1).view(np.uint8)


def serialize_kv_layer(cfg: ModelConfig, state, slot: int, t0: int,
                       t1: int, layer: int) -> np.ndarray:
    """One attention layer's KV rows -> (t1-t0, row_bytes) uint8."""
    key, idx = _kv_rows(cfg)[layer]
    comp = state[key]
    if cfg.attn_variant == "mla":
        c = np.asarray(comp["c"][idx + (slot, slice(t0, t1))])
        kr = np.asarray(comp["krope"][idx + (slot, slice(t0, t1))])
        return np.concatenate([_to_bytes(c), _to_bytes(kr)], axis=-1)
    k = np.asarray(comp["k"][idx + (slot, slice(t0, t1))])
    v = np.asarray(comp["v"][idx + (slot, slice(t0, t1))])
    return np.concatenate([_to_bytes(k), _to_bytes(v)], axis=-1)


def serialize_kv(cfg: ModelConfig, state, slot: int, t0: int,
                 t1: int) -> np.ndarray:
    """-> (n_attn_layers, t1-t0, row_bytes) uint8."""
    return np.stack([serialize_kv_layer(cfg, state, slot, t0, t1, li)
                     for li in range(len(_kv_rows(cfg)))], axis=0)


@functools.partial(jax.jit, donate_argnums=0)
def _place_rows(group, at, rows):
    """``group``'s leaves, each (*stack, b, S, ...), with ``rows[key]``
    (T, ...) written at ``at`` = (*stack, slot, t0) and on: traced
    indices, so one program per row count; the group is donated."""
    out = {}
    for key, a in group.items():
        r = rows[key].astype(a.dtype)
        r = r.reshape((1,) * (len(at) - 1) + r.shape)
        out[key] = jax.lax.dynamic_update_slice(
            a, r, tuple(at) + (0,) * (a.ndim - len(at)))
    return out


def put_kv_layer(cfg: ModelConfig, state, slot: int, t0: int, layer: int,
                 row: np.ndarray):
    """Write one layer's (T, row_bytes) uint8 rows into the state — the
    per-LayerBlock HBM placement step of layerwise loading — taking the
    state over: the buffers of the layer group written (``state[key]``
    of ``_kv_rows``) are updated in place and deleted.  ``slot``, ``t0``
    and the layer's stack index are traced, so every layer of a group
    runs one program per row count."""
    key, idx = _kv_rows(cfg)[layer]
    T = row.shape[0]
    dt = jnp.dtype(cfg.kv_cache_dtype)
    if cfg.attn_variant == "mla":
        r = cfg.mla.kv_lora_rank
        c = row[:, :r * dt.itemsize].copy().view(dt).reshape(T, r)
        kr = row[:, r * dt.itemsize:].copy().view(dt).reshape(
            T, cfg.mla.rope_head_dim)
        upd = {"c": c, "krope": kr}
    else:
        half = cfg.n_kv_heads * cfg.head_dim * dt.itemsize
        k = row[:, :half].copy().view(dt).reshape(
            T, cfg.n_kv_heads, cfg.head_dim)
        v = row[:, half:].copy().view(dt).reshape(
            T, cfg.n_kv_heads, cfg.head_dim)
        upd = {"k": k, "v": v}
    new_state = dict(state)
    new_state[key] = _place_rows(state[key], idx + (slot, t0), upd)
    return new_state


def _copy_group(state, key):
    return {**state, key: jax.tree.map(jnp.copy, state[key])}


def deserialize_kv_layer(cfg: ModelConfig, state, slot: int, t0: int,
                         layer: int, row: np.ndarray):
    """:func:`put_kv_layer` on a copy of the layer group it writes: the
    state passed in stays valid.  The write is the same program."""
    state = _copy_group(state, _kv_rows(cfg)[layer][0])
    return put_kv_layer(cfg, state, slot, t0, layer, row)


def deserialize_kv(cfg: ModelConfig, state, slot: int, t0: int,
                   kv_bytes: np.ndarray):
    """Write (L, T, row_bytes) uint8 back into the padded state buffers
    (a copy: the state passed in stays valid)."""
    rows = _kv_rows(cfg)
    L = kv_bytes.shape[0]
    assert L == len(rows), (L, len(rows))
    for key in {key for key, _ in rows}:
        state = _copy_group(state, key)
    for li in range(L):
        state = put_kv_layer(cfg, state, slot, t0, li, kv_bytes[li])
    return state


# ---------------------------------------------------------------------------
# layerwise double-buffered delivery (paper §4.1)
# ---------------------------------------------------------------------------


def layer_stream(cfg: ModelConfig, blocks: List[np.ndarray],
                 tm: Optional[TrafficManager] = None,
                 tclass: TrafficClass = TrafficClass.KV_TRANSFER,
                 tracer=None, track: str = "kvio"
                 ) -> Iterator[Tuple[int, np.ndarray]]:
    """Double-buffered per-layer LayerBlock stream from FullBlock pages.

    ``blocks``: the request's hit FullBlocks, each (L, page_tokens,
    row_bytes) uint8.  Yields ``(layer, rows)`` with ``rows`` of shape
    (n_blocks·page_tokens, row_bytes), gathered through the
    kernels/kv_gather.py Pallas kernel (``kernels.ops``: compiled on
    TPU, interpret mode on CPU) so the HBM-placement path is the same
    pipelined-DMA gather the TPU runs.

    Pipeline shape: layer ``i+1``'s gather is *submitted* to the
    TrafficManager before layer ``i`` is yielded, so while the consumer
    installs layer ``i`` the next LayerBlock sits in flight on the KV
    virtual lane — at most two layer buffers are ever live, exactly the
    double-buffering the paper overlaps with per-layer prefill compute.
    The TrafficManager charges each gather's bytes to the KV traffic
    class, exercising the §5 ordering/doorbell-batching machinery.

    With a ``tracer`` (repro.obs.Tracer) the stack and upload of the
    pool is the host region ``pe.install.upload`` and each layer's
    gather, rows brought to the host included, ``pe.install.gather``,
    both on ``track``.
    """
    from repro.kernels.ops import kv_layer_gather

    n_l = n_attn_layers(cfg)
    if not blocks or n_l == 0:
        return
    if tracer is None:
        pool = jnp.asarray(np.stack(blocks))  # (n_blocks, L, pt, row)
    else:
        with tracer.region(track, "pe.install.upload",
                           bytes=sum(b.nbytes for b in blocks)):
            pool = jnp.asarray(np.stack(blocks))
    n, _, pt, row = pool.shape
    table = jnp.arange(n, dtype=jnp.int32)
    layer_bytes = int(n * pt * row)
    own_tm = tm is None
    if own_tm:
        tm = TrafficManager()
    buf: Dict[int, np.ndarray] = {}

    def gather(layer: int) -> np.ndarray:
        out = kv_layer_gather(pool, table, layer=layer)
        return np.asarray(out).reshape(n * pt, row)

    def fetch(layer: int):
        if tracer is None:
            buf[layer] = gather(layer)
            return
        with tracer.region(track, "pe.install.gather", layer=layer,
                           bytes=layer_bytes):
            buf[layer] = gather(layer)

    tm.submit(lambda: fetch(0), layer_bytes, tclass)
    for li in range(n_l):
        tm.drain()                            # layer li has landed
        if li + 1 < n_l:                      # layer li+1 goes in flight
            tm.submit(lambda nxt=li + 1: fetch(nxt), layer_bytes, tclass)
        yield li, buf.pop(li)
