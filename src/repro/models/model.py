"""Full-model assembly: embedding, per-family layer stacks (lax.scan over
stacked block params), logits, plus the three step flavours the system
needs:

* ``forward``       — full-sequence (train / whole-prompt prefill);
                      optionally returns the KV/state caches it produced.
* ``decode_step``   — one token per sequence against a decode state;
                      dense/vlm update their stacked cache in place
                      (one K and V row per sequence per layer).
* ``append_forward``— engine path: prefill an appended chunk against an
                      existing (padded) prefix KV — the agentic
                      short-append pattern the paper optimises.

Decode state layout (stacked along layer groups, mirroring the param
stacking so a single scan consumes both):

* dense/vlm:  {"kv": {"k": (L,b,S,hkv,dh), "v": ...}}, carried whole by
              the decode and append scans
* moe:        {"dense": {...(f)}, "pre": {...(n_super,p-1)}, "moe": {...(n_super)}}
* mla:        {"c": (L,b,S,r), "krope": (L,b,S,rd)}
* ssm:        {stacked ssm state dicts (L,...)}
* hybrid:     {"mamba": (n_super, period, ...), "shared": {"k","v": (n_apps,b,S,hkv,dh)}}

The moe, ssm and hybrid decodes and appends stream each layer's cache
through the scan's xs/ys.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers, mla as mla_lib, moe as moe_lib, ssm as ssm_lib
from repro.models.layers import rms_norm
from repro.models.sharding import constrain, current_mesh

BIG_WINDOW = 1 << 30


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------


def embed(params, cfg: ModelConfig, inputs):
    """Token ids (b,s) int -> (b,s,d); or precomputed frontend embeddings
    (b,s,frontend_dim) float -> (b,s,d) via the connector projection."""
    e = params["embed"]
    if inputs.ndim == 3:
        assert cfg.frontend_embed_dim, cfg.name
        h = jnp.einsum("bsf,fd->bsd", inputs.astype(e["tok"].dtype),
                       e["frontend_proj"])
    else:
        h = e["tok"][inputs]
    if cfg.embed_scale != 1.0:
        h = h * jnp.asarray(cfg.embed_scale, h.dtype)
    return constrain(h, "batch", "seq", None)


def logits_from_hidden(params, cfg: ModelConfig, h):
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    if cfg.tie_embeddings:
        out = jnp.einsum("bsd,vd->bsv", h, params["embed"]["tok"])
    else:
        out = jnp.einsum("bsd,dv->bsv", h, params["lm_head"])
    out = layers._softcap(out.astype(jnp.float32), cfg.final_logit_softcap)
    return constrain(out, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# Block applies
# ---------------------------------------------------------------------------


def _window_for(cfg: ModelConfig, is_local):
    """None if the arch has no local layers; else a traced scalar window."""
    if not cfg.local_global_period and not cfg.local_window:
        return None
    return jnp.where(is_local, cfg.local_window, BIG_WINDOW)


def _attn_full(p, cfg: ModelConfig, x, positions, is_local):
    if cfg.attn_variant == "mla":
        o, kv = mla_lib.mla_full(p, cfg, x, positions, causal=cfg.causal)
        return o, {"c": kv[0], "krope": kv[1]}
    q, k, v = layers.gqa_qkv(p, cfg, x, positions)
    o = layers.attend(q, k, v, causal=cfg.causal,
                      window=_window_for(cfg, is_local),
                      softcap=cfg.attn_logit_softcap)
    o = constrain(o, "batch", "seq", "heads", "head_dim")
    return layers.attn_out(p, o), {"k": k, "v": v}


# Lanes of a TPU vector register.  One chip stores the stacked cache
# (L, b, S, hkv, dh) with the sequence axis in lanes, so dh = 64 is not
# padded; a write narrower than one lane tile of that axis, or a
# scatter, makes XLA relayout the whole cache twice a step.
_LANES = 128


def _put_rows(buf, layer, lengths, rows):
    """``buf`` (L, b, S, ...) with ``rows[i]`` written at
    ``[layer, i, lengths[i]]``, in place when ``buf`` is a donated carry.

    Unpartitioned, each sequence's row goes in through the lane-aligned
    window of tokens that holds it: read, blended, written back.  Over a
    mesh (:func:`repro.models.sharding.current_mesh`) the rows go in by
    one scatter, which partitions along batch and sequence where a
    per-sequence window would gather the whole cache to every device.
    A position at or past S writes nothing either way."""
    b, s = buf.shape[1], buf.shape[2]
    rows = rows.astype(buf.dtype)
    if current_mesh() is not None:
        return buf.at[layer, jnp.arange(b), lengths].set(rows)
    w = min(_LANES, s)
    starts = jnp.minimum(lengths // w * w, s - w)
    zeros = (jnp.zeros((), lengths.dtype),) * (buf.ndim - 3)
    unit = (1,) * (buf.ndim - 3)
    for i in range(b):
        at = (layer, jnp.asarray(i, lengths.dtype), starts[i]) + zeros
        win = jax.lax.dynamic_slice(buf, at, (1, 1, w) + buf.shape[3:])
        hit = (jnp.arange(w) == lengths[i] - starts[i]).reshape(
            (1, 1, w) + unit)
        buf = jax.lax.dynamic_update_slice(
            buf, jnp.where(hit, rows[i], win), at)
    return buf


def _attn_decode(p, cfg: ModelConfig, x, cache, lengths, is_local,
                 layer=None):
    """x (b,1,d); cache holds padded buffers; lengths (b,) = tokens already
    cached.  Writes the new token at index `lengths`.  With ``layer`` the
    buffers are the whole stack (L,b,S,...): the token's rows go to
    ``[layer, b, lengths]`` and attention reads layer ``layer``."""
    b = x.shape[0]
    bidx = jnp.arange(b)
    if cfg.attn_variant == "mla":
        c_new, kr_new = mla_lib.mla_latent(p, cfg, x, lengths[:, None])
        c_cache = cache["c"].at[bidx, lengths].set(c_new[:, 0])
        kr_cache = cache["krope"].at[bidx, lengths].set(kr_new[:, 0])
        o = mla_lib.mla_decode(p, cfg, x, c_cache, kr_cache, lengths + 1)
        return o, {"c": c_cache, "krope": kr_cache}
    q, k, v = layers.gqa_qkv(p, cfg, x, lengths[:, None])
    if layer is None:
        k_cache = cache["k"].at[bidx, lengths].set(k[:, 0])
        v_cache = cache["v"].at[bidx, lengths].set(v[:, 0])
    else:
        k_cache = _put_rows(cache["k"], layer, lengths, k[:, 0])
        v_cache = _put_rows(cache["v"], layer, lengths, v[:, 0])
    k_read, v_read = ((k_cache, v_cache) if layer is None
                      else (k_cache[layer], v_cache[layer]))
    o = layers.decode_attend(q, k_read, v_read, lengths + 1,
                             window=_window_for(cfg, is_local),
                             softcap=cfg.attn_logit_softcap)
    return layers.attn_out(p, o), {"k": k_cache, "v": v_cache}


def _dense_block(p, cfg: ModelConfig, h, *, mode, positions=None,
                 cache=None, lengths=None, is_local=False, layer=None,
                 moe_impl=None, is_moe=False, capacity_factor=1.25):
    """One transformer block (attention + FFN/MoE) in full or decode mode;
    ``layer`` as in :func:`_attn_decode`."""
    xn = rms_norm(h, p["ln1"], cfg.rms_norm_eps)
    if mode == "full":
        attn, kv = _attn_full(p["attn"], cfg, xn, positions, is_local)
    else:
        attn, kv = _attn_decode(p["attn"], cfg, xn, cache, lengths,
                                is_local, layer)
    if cfg.post_attn_norm:
        attn = rms_norm(attn, p["ln1b"], cfg.rms_norm_eps)
    h = h + attn * cfg.ffn_mult
    xn = rms_norm(h, p["ln2"], cfg.rms_norm_eps)
    if is_moe:
        f = moe_lib.moe_ffn(p["moe"], cfg, xn, impl=moe_impl,
                            capacity_factor=capacity_factor)
    else:
        f = layers.ffn(p["ffn"], cfg, xn)
    if cfg.post_attn_norm:
        f = rms_norm(f, p["ln2b"], cfg.rms_norm_eps)
    h = h + f * cfg.ffn_mult
    return constrain(h, "batch", "seq", None), kv


def _mamba_block(p, cfg: ModelConfig, h, *, mode, state=None):
    xn = rms_norm(h, p["ln"], cfg.rms_norm_eps)
    if mode == "full":
        # ssd_scan returns {"ssm", "conv_x", "conv_B", "conv_C"} — the full
        # recurrent state needed to continue decoding after a prefill.
        out, new_state = ssm_lib.ssd_scan(
            p, cfg, xn,
            initial_state=None if state is None else state["ssm"])
    else:
        out, new_state = ssm_lib.ssm_decode_step(p, cfg, xn, state)
    return h + out, new_state


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def _is_local_arr(cfg: ModelConfig):
    return jnp.asarray([k == "local_attn" for k in cfg.layer_kinds()],
                       dtype=bool)


REMAT_POLICIES = {
    "full": lambda: jax.checkpoint_policies.nothing_saveable,
    "dots": lambda: jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": lambda:
        jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


def _maybe_remat(fn, remat):
    """remat: False | True ('full') | policy name from REMAT_POLICIES."""
    if not remat:
        return fn
    name = "full" if remat is True else remat
    return jax.checkpoint(fn, policy=REMAT_POLICIES[name]())


def forward(params, cfg: ModelConfig, inputs, *, positions=None,
            return_state: bool = False, moe_impl: str = "ragged",
            remat: bool = False, capacity_factor: float = 1.25,
            last_only: bool = False):
    """Full-sequence forward.  Returns (logits, state_or_None).

    ``return_state`` also returns the per-layer KV / SSM state produced —
    i.e. the prompt cache a prefill engine hands to a decode engine.
    Note: full-mode KV is *exact-length* (b,s,...); decode buffers are
    padded separately by the engine when it installs the cache.
    """
    b, s = inputs.shape[:2]
    if positions is None:
        positions = jnp.arange(s)
    h = embed(params, cfg, inputs)
    fam = cfg.family

    if fam in ("dense", "vlm", "encoder"):
        is_local = _is_local_arr(cfg)

        def body(hh, xs):
            blk, loc = xs
            hh, kv = _dense_block(blk, cfg, hh, mode="full",
                                  positions=positions, is_local=loc)
            return hh, (kv if return_state else 0)

        h, kvs = jax.lax.scan(_maybe_remat(body, remat), h,
                              (params["blocks"], is_local))
        state = {"kv": kvs} if return_state else None

    elif fam == "moe":
        m = cfg.moe
        state_parts = {}

        def dense_body(hh, blk):
            hh, kv = _dense_block(blk, cfg, hh, mode="full",
                                  positions=positions)
            return hh, (kv if return_state else 0)

        def moe_body(hh, blk):
            hh, kv = _dense_block(blk, cfg, hh, mode="full",
                                  positions=positions, is_moe=True,
                                  moe_impl=moe_impl,
                                  capacity_factor=capacity_factor)
            return hh, (kv if return_state else 0)

        if m.first_k_dense:
            h, kv_d = jax.lax.scan(_maybe_remat(dense_body, remat), h,
                                   params["dense_blocks"])
            state_parts["dense"] = kv_d

        if m.period > 1:
            def super_body(hh, xs):
                hh, kv_pre = jax.lax.scan(dense_body, hh, xs["pre"])
                hh, kv_moe = moe_body(hh, xs["moe"])
                return hh, ({"pre": kv_pre, "moe": kv_moe}
                            if return_state else 0)

            h, kv_s = jax.lax.scan(_maybe_remat(super_body, remat), h,
                                   params["super_blocks"])
            if return_state:
                state_parts.update(kv_s)
        else:
            h, kv_moe = jax.lax.scan(_maybe_remat(moe_body, remat), h,
                                     params["super_blocks"]["moe"])
            state_parts["moe"] = kv_moe
        state = state_parts if return_state else None

    elif fam == "ssm":
        def body(hh, blk):
            hh, st = _mamba_block(blk, cfg, hh, mode="full")
            return hh, (st if return_state else 0)

        h, sts = jax.lax.scan(_maybe_remat(body, remat), h, params["blocks"])
        state = {"mamba": sts} if return_state else None

    elif fam == "hybrid":
        shared = params["shared_block"]
        is_local = jnp.asarray(False)

        def super_body(hh, blks):
            def inner(hh2, blk):
                hh2, st = _mamba_block(blk, cfg, hh2, mode="full")
                return hh2, (st if return_state else 0)

            hh, sts = jax.lax.scan(inner, hh, blks)
            hh, kv = _dense_block(shared, cfg, hh, mode="full",
                                  positions=positions, is_local=is_local)
            return hh, ({"mamba": sts, "shared": kv} if return_state else 0)

        h, st = jax.lax.scan(_maybe_remat(super_body, remat), h,
                             params["blocks"])
        state = st if return_state else None
    else:  # pragma: no cover
        raise ValueError(fam)

    if last_only:
        h = h[:, -1:]            # prefill: only the next-token logits matter
    return logits_from_hidden(params, cfg, h), state


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_decode_state(cfg: ModelConfig, batch: int, max_seq: int,
                      abstract: bool = False) -> Dict[str, Any]:
    """Zero-initialised (or ShapeDtypeStruct) decode caches."""
    kvd = jnp.dtype(cfg.kv_cache_dtype)

    def kv(n_stack=()):
        shape = tuple(n_stack) + (batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jax.ShapeDtypeStruct(shape, kvd) if abstract
                else jnp.zeros(shape, kvd),
                "v": jax.ShapeDtypeStruct(shape, kvd) if abstract
                else jnp.zeros(shape, kvd)}

    fam = cfg.family
    if fam in ("dense", "vlm"):
        return {"kv": kv((cfg.n_layers,))}
    if fam == "moe":
        m = cfg.moe
        n_super = (cfg.n_layers - m.first_k_dense) // m.period
        if cfg.attn_variant == "mla":
            r, rd = cfg.mla.kv_lora_rank, cfg.mla.rope_head_dim

            def mk(n_stack, dim):
                shape = tuple(n_stack) + (batch, max_seq, dim)
                return (jax.ShapeDtypeStruct(shape, kvd) if abstract
                        else jnp.zeros(shape, kvd))

            out = {}
            if m.first_k_dense:
                out["dense"] = {"c": mk((m.first_k_dense,), r),
                                "krope": mk((m.first_k_dense,), rd)}
            out["moe"] = {"c": mk((n_super,), r), "krope": mk((n_super,), rd)}
            if m.period > 1:
                out["pre"] = {"c": mk((n_super, m.period - 1), r),
                              "krope": mk((n_super, m.period - 1), rd)}
            return out
        out = {}
        if m.first_k_dense:
            out["dense"] = kv((m.first_k_dense,))
        out["moe"] = kv((n_super,))
        if m.period > 1:
            out["pre"] = kv((n_super, m.period - 1))
        return out
    if fam == "ssm":
        st = ssm_lib.init_ssm_state(cfg, batch)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), st)
        if abstract:
            stacked = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), stacked)
        return {"mamba": stacked}
    if fam == "hybrid":
        n_super = cfg.n_layers // cfg.hybrid_period
        st = ssm_lib.init_ssm_state(cfg, batch)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(
                a, (n_super, cfg.hybrid_period) + a.shape).copy(), st)
        if abstract:
            stacked = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), stacked)
        return {"mamba": stacked, "shared": kv((n_super,))}
    raise ValueError(fam)  # pragma: no cover  (encoder: no decode)


def decode_step(params, cfg: ModelConfig, tokens, state, lengths, *,
                moe_impl: str = "ragged", capacity_factor: float = 1.25):
    """One decode step.  tokens (b,) int32; lengths (b,) = #tokens already
    cached.  Returns (logits (b, vocab), new_state).

    Dense/vlm: the stacked cache rides the layer scan's carry, and each
    layer writes only the step's K and V row per sequence into it
    (``[layer, b, lengths]``) and reads its own layer from it.  Jitted
    with the state donated (``engines/runtime.py``), XLA updates the
    cache in place: no layer's slice is copied out or written back, and
    one cache is live, not two.  The other families stream their
    per-layer caches through the scan's xs/ys.
    """
    assert cfg.supports_decode, cfg.name
    h = embed(params, cfg, tokens[:, None])
    fam = cfg.family

    if fam in ("dense", "vlm"):
        def body(carry, xs):
            hh, kv = carry
            blk, loc, li = xs
            hh, kv = _dense_block(blk, cfg, hh, mode="decode", cache=kv,
                                  lengths=lengths, is_local=loc, layer=li)
            return (hh, kv), None

        (h, kvs), _ = jax.lax.scan(
            body, (h, state["kv"]),
            (params["blocks"], _is_local_arr(cfg), jnp.arange(cfg.n_layers)))
        new_state = {"kv": kvs}

    elif fam == "moe":
        m = cfg.moe
        new_state = {}

        def dense_body(hh, xs):
            blk, cache = xs
            hh, kv = _dense_block(blk, cfg, hh, mode="decode", cache=cache,
                                  lengths=lengths)
            return hh, kv

        def moe_body(hh, xs):
            blk, cache = xs
            hh, kv = _dense_block(blk, cfg, hh, mode="decode", cache=cache,
                                  lengths=lengths, is_moe=True,
                                  moe_impl=moe_impl,
                                  capacity_factor=capacity_factor)
            return hh, kv

        if m.first_k_dense:
            h, kv_d = jax.lax.scan(dense_body, h,
                                   (params["dense_blocks"], state["dense"]))
            new_state["dense"] = kv_d
        if m.period > 1:
            def super_body(hh, xs):
                blks, caches = xs
                hh, kv_pre = jax.lax.scan(dense_body, hh,
                                          (blks["pre"], caches["pre"]))
                hh, kv_moe = moe_body(hh, (blks["moe"], caches["moe"]))
                return hh, {"pre": kv_pre, "moe": kv_moe}

            h, kv_s = jax.lax.scan(
                super_body, h,
                (params["super_blocks"],
                 {"pre": state["pre"], "moe": state["moe"]}))
            new_state.update(kv_s)
        else:
            h, kv_moe = jax.lax.scan(
                moe_body, h, (params["super_blocks"]["moe"], state["moe"]))
            new_state["moe"] = kv_moe

    elif fam == "ssm":
        def body(hh, xs):
            blk, st = xs
            hh, st2 = _mamba_block(blk, cfg, hh, mode="decode", state=st)
            return hh, st2

        h, sts = jax.lax.scan(body, h, (params["blocks"], state["mamba"]))
        new_state = {"mamba": sts}

    elif fam == "hybrid":
        shared = params["shared_block"]
        is_local = jnp.asarray(False)

        def super_body(hh, xs):
            blks, sts, cache = xs

            def inner(hh2, xs2):
                blk, st = xs2
                hh2, st2 = _mamba_block(blk, cfg, hh2, mode="decode",
                                        state=st)
                return hh2, st2

            hh, sts2 = jax.lax.scan(inner, hh, (blks, sts))
            hh, kv = _dense_block(shared, cfg, hh, mode="decode", cache=cache,
                                  lengths=lengths, is_local=is_local)
            return hh, (sts2, kv)

        h, (sts, kvs) = jax.lax.scan(
            super_body, h,
            (params["blocks"], state["mamba"], state["shared"]))
        new_state = {"mamba": sts, "shared": kvs}
    else:  # pragma: no cover
        raise ValueError(fam)

    logits = logits_from_hidden(params, cfg, h)[:, 0]
    return logits, new_state


# ---------------------------------------------------------------------------
# Append (engine prefill of a chunk against existing padded caches)
# ---------------------------------------------------------------------------


def _put_chunk(buf, layer, lengths, rows):
    """``buf`` (L, b, S, ...) with ``rows[i]`` (s, ...) written at
    ``[layer, i, lengths[i]:lengths[i] + s]``, in place when ``buf`` is a
    donated carry.  Unlike one row (:func:`_put_rows`), a chunk's
    dynamic-update-slice keeps the stored layout (sequence in lanes): no
    relayout of the cache (compiled for a v5e, minicpm-2b and
    qwen1.5-0.5b).  The chunk has to end at or before S: a later start
    is clamped, which would shift the chunk back over earlier rows, so
    ``PrefillEngine`` refuses a prompt longer than its state."""
    rows = rows.astype(buf.dtype)
    zeros = (jnp.zeros((), lengths.dtype),) * (buf.ndim - 3)
    for i in range(buf.shape[1]):
        at = (layer, jnp.asarray(i, lengths.dtype), lengths[i]) + zeros
        buf = jax.lax.dynamic_update_slice(buf, rows[i][None, None], at)
    return buf


def _attn_append(p, cfg: ModelConfig, x, cache, lengths, is_local,
                 layer=None):
    """x (b,s,d); writes the chunk's K/V at [lengths, lengths+s).  With
    ``layer`` the buffers are the whole stack (L,b,S,...), as in
    :func:`_attn_decode`."""
    b, s, _ = x.shape
    bidx = jnp.arange(b)[:, None]
    positions = lengths[:, None] + jnp.arange(s)[None, :]
    if cfg.attn_variant == "mla":
        o, (c, kr) = mla_lib.mla_append(p, cfg, x, cache["c"],
                                        cache["krope"], lengths)
        return o, {"c": c, "krope": kr}
    q, k, v = layers.gqa_qkv(p, cfg, x, positions)
    if layer is None:
        k_cache = cache["k"].at[bidx, positions].set(
            k.astype(cache["k"].dtype))
        v_cache = cache["v"].at[bidx, positions].set(
            v.astype(cache["v"].dtype))
        k_read, v_read = k_cache, v_cache
    else:
        k_cache = _put_chunk(cache["k"], layer, lengths, k)
        v_cache = _put_chunk(cache["v"], layer, lengths, v)
        k_read, v_read = k_cache[layer], v_cache[layer]
    o = layers.append_attend(q, k_read, v_read, lengths,
                             window=_window_for(cfg, is_local),
                             softcap=cfg.attn_logit_softcap)
    return layers.attn_out(p, o), {"k": k_cache, "v": v_cache}


def _append_block(p, cfg, h, cache, lengths, is_local=False, is_moe=False,
                  moe_impl="ragged", capacity_factor=1.25, layer=None):
    xn = rms_norm(h, p["ln1"], cfg.rms_norm_eps)
    attn, kv = _attn_append(p["attn"], cfg, xn, cache, lengths, is_local,
                            layer)
    if cfg.post_attn_norm:
        attn = rms_norm(attn, p["ln1b"], cfg.rms_norm_eps)
    h = h + attn * cfg.ffn_mult
    xn = rms_norm(h, p["ln2"], cfg.rms_norm_eps)
    if is_moe:
        f = moe_lib.moe_ffn(p["moe"], cfg, xn, impl=moe_impl,
                            capacity_factor=capacity_factor)
    else:
        f = layers.ffn(p["ffn"], cfg, xn)
    if cfg.post_attn_norm:
        f = rms_norm(f, p["ln2b"], cfg.rms_norm_eps)
    return h + f * cfg.ffn_mult, kv


def _mamba_append(p, cfg, h, state):
    """Multi-token chunk through a mamba block with carried state."""
    xn = rms_norm(h, p["ln"], cfg.rms_norm_eps)
    # run the chunked scan from the carried state; conv tails carried too
    out, new_state = ssm_lib.ssd_scan_with_tails(p, cfg, xn, state)
    return h + out, new_state


def append_step(params, cfg: ModelConfig, tokens, state, lengths, *,
                moe_impl: str = "ragged", capacity_factor: float = 1.25,
                last_only: bool = False):
    """Prefill an append chunk against existing decode state.

    tokens (b, s_app) int32 (or (b, s_app, frontend_dim) embeddings);
    lengths (b,) = tokens already cached.  Returns
    (logits (b, s_app, vocab), new_state); with ``last_only`` the logits
    of the chunk's last position only, (b, 1, vocab), which is all the
    engine samples.  This is the engine's layerwise-prefill compute
    step.  Dense/vlm carry the stacked cache through the layer scan, as
    :func:`decode_step` does: each layer writes its chunk's rows in place
    (jitted with the state donated) and reads its own layer.  The other
    families consume and produce each layer's cache per scan iteration.
    """
    h = embed(params, cfg, tokens)
    fam = cfg.family

    if fam in ("dense", "vlm"):
        def body(carry, xs):
            hh, kv = carry
            blk, loc, li = xs
            hh, kv = _append_block(blk, cfg, hh, kv, lengths, is_local=loc,
                                   layer=li)
            return (hh, kv), None

        (h, kvs), _ = jax.lax.scan(
            body, (h, state["kv"]),
            (params["blocks"], _is_local_arr(cfg), jnp.arange(cfg.n_layers)))
        new_state = {"kv": kvs}

    elif fam == "moe":
        m = cfg.moe
        new_state = {}

        def dense_body(hh, xs):
            blk, cache = xs
            hh, kv = _append_block(blk, cfg, hh, cache, lengths)
            return hh, kv

        def moe_body(hh, xs):
            blk, cache = xs
            hh, kv = _append_block(blk, cfg, hh, cache, lengths, is_moe=True,
                                   moe_impl=moe_impl,
                                   capacity_factor=capacity_factor)
            return hh, kv

        if m.first_k_dense:
            h, kv_d = jax.lax.scan(dense_body, h,
                                   (params["dense_blocks"], state["dense"]))
            new_state["dense"] = kv_d
        if m.period > 1:
            def super_body(hh, xs):
                blks, caches = xs
                hh, kv_pre = jax.lax.scan(dense_body, hh,
                                          (blks["pre"], caches["pre"]))
                hh, kv_moe = moe_body(hh, (blks["moe"], caches["moe"]))
                return hh, {"pre": kv_pre, "moe": kv_moe}

            h, kv_s = jax.lax.scan(
                super_body, h,
                (params["super_blocks"],
                 {"pre": state["pre"], "moe": state["moe"]}))
            new_state.update(kv_s)
        else:
            h, kv_moe = jax.lax.scan(
                moe_body, h, (params["super_blocks"]["moe"], state["moe"]))
            new_state["moe"] = kv_moe

    elif fam == "ssm":
        def body(hh, xs):
            blk, st = xs
            hh, st2 = _mamba_append(blk, cfg, hh, st)
            return hh, st2

        h, sts = jax.lax.scan(body, h, (params["blocks"], state["mamba"]))
        new_state = {"mamba": sts}

    elif fam == "hybrid":
        shared = params["shared_block"]
        is_local = jnp.asarray(False)

        def super_body(hh, xs):
            blks, sts, cache = xs

            def inner(hh2, xs2):
                blk, st = xs2
                hh2, st2 = _mamba_append(blk, cfg, hh2, st)
                return hh2, st2

            hh, sts2 = jax.lax.scan(inner, hh, (blks, sts))
            hh, kv = _append_block(shared, cfg, hh, cache, lengths,
                                   is_local=is_local)
            return hh, (sts2, kv)

        h, (sts, kvs) = jax.lax.scan(
            super_body, h,
            (params["blocks"], state["mamba"], state["shared"]))
        new_state = {"mamba": sts, "shared": kvs}
    else:  # pragma: no cover
        raise ValueError(fam)

    if last_only:
        h = h[:, -1:]
    return logits_from_hidden(params, cfg, h), new_state


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def lm_loss(logits, labels, mask=None):
    """Mean next-token cross-entropy.  logits (b,s,v) f32, labels (b,s)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
