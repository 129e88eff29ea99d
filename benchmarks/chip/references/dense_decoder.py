"""Plain reference of a dense decoder-only transformer (Llama, Qwen2, MiniCPM).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernels, no cache, no batching.  It reads the configuration file's
published keys and imports nothing of the program under test.

Per layer, with ``s = scale_depth / sqrt(num_hidden_layers)`` (1 where
the config has no ``scale_depth``)::

    x = rmsnorm(h) * ln1
    q, k, v = x Wq (+ bq), x Wk (+ bk), x Wv (+ bv);  rope(q), rope(k)
    h = h + s * softmax(q k^T / sqrt(head_dim) + causal) v Wo
    x = rmsnorm(h) * ln2
    h = h + s * (silu(x Wg) * (x Wu)) Wd

with ``h0 = scale_emb * E[tokens]`` and logits
``rmsnorm(h) * final_norm  E^T / (hidden_size / dim_model_base)`` (tied
embeddings; each scale is 1 where the config does not name it).  RoPE
rotates the two halves of each head (``rotate_half``).

:func:`weights` makes the served weights from a seed in one jitted call
on the device, in bfloat16, in the tree layout the serving program
takes; the reference makes them again from the same seed rather than
reading the program's copy.  ``precision="fp8"`` is the control: every
linear layer's operands rounded to float8 (e4m3, one scale per tensor),
the step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    theta: float
    emb_scale: float
    depth_scale: float
    logit_scale: float
    qkv_bias: bool


def dims(config: dict) -> Dims:
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    layers = config["num_hidden_layers"]
    if not config.get("tie_word_embeddings", False):
        raise ValueError("dense_decoder covers tied embeddings only")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("dense_decoder covers the SiLU-gated FFN only")
    return Dims(
        d=d, layers=layers, heads=heads,
        kv_heads=config.get("num_key_value_heads", heads),
        head_dim=config.get("head_dim", d // heads),
        ff=config["intermediate_size"], vocab=config["vocab_size"],
        eps=float(config["rms_norm_eps"]),
        theta=float(config.get("rope_theta", 10000.0)),
        emb_scale=float(config.get("scale_emb", 1.0)),
        depth_scale=(float(config["scale_depth"]) / math.sqrt(layers)
                     if "scale_depth" in config else 1.0),
        logit_scale=(d / config["dim_model_base"]
                     if "dim_model_base" in config else 1.0),
        qkv_bias=bool(config.get("qkv_bias", False)))


def seed_key(seed: int):
    """A PRNG key from a whole number of any size."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _shapes(m: Dims) -> dict:
    L, d, h, kv, dh, f = m.layers, m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    attn = {"wq": ((L, d, h, dh), d), "wk": ((L, d, kv, dh), d),
            "wv": ((L, d, kv, dh), d), "wo": ((L, h * dh, d), h * dh)}
    if m.qkv_bias:
        attn.update(bq=((L, h, dh), "bias"), bk=((L, kv, dh), "bias"),
                    bv=((L, kv, dh), "bias"))
    return {"embed": {"tok": ((m.vocab, d), "embed")},
            "final_norm": ((d,), "norm"),
            "blocks": {"ln1": ((L, d), "norm"), "ln2": ((L, d), "norm"),
                       "attn": attn,
                       "ffn": {"wi_gate": ((L, d, f), d),
                               "wi_up": ((L, d, f), d),
                               "wo": ((L, f, d), f)}}}


def _leaf(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        v = 1.0 + 0.1 * z
    elif kind == "bias":
        v = 0.1 * z
    elif kind == "embed":
        v = 0.02 * z
    else:                                       # projection: fan-in
        v = z / math.sqrt(kind)
    return v.astype(jnp.bfloat16)


def _is_spec(x):
    return isinstance(x, tuple) and isinstance(x[0], tuple)


@functools.partial(jax.jit, static_argnames=("m",))
def _make(key, *, m: Dims):
    leaves, tree = jax.tree.flatten(_shapes(m), is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        tree, [_leaf(k, s, kind) for k, (s, kind) in zip(keys, leaves)])


def weights(config: dict, seed: int):
    """The served weights, made on the device in one jitted call."""
    return _make(seed_key(seed), m=dims(config))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fp8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(spec, x, w, precision):
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, dh, 2,
                                                  dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m: Dims, precision, h, blk, pos):
    S = h.shape[0]
    a = blk["attn"]
    x = _rmsnorm(h, blk["ln1"], m.eps)
    q = _linear("sd,dhk->shk", x, a["wq"], precision)
    k = _linear("sd,dhk->shk", x, a["wk"], precision)
    v = _linear("sd,dhk->shk", x, a["wv"], precision)
    if m.qkv_bias:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q, k = _rope(q, pos, m.theta), _rope(k, pos, m.theta)
    g = m.heads // m.kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / math.sqrt(m.head_dim)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(S, -1)
    h = h + m.depth_scale * _linear("sm,md->sd", o, a["wo"], precision)
    f = blk["ffn"]
    x = _rmsnorm(h, blk["ln2"], m.eps)
    u = jax.nn.silu(_linear("sd,df->sf", x, f["wi_gate"], precision)) \
        * _linear("sd,df->sf", x, f["wi_up"], precision)
    return h + m.depth_scale * _linear("sf,fd->sd", u, f["wo"], precision)


def _forward(m: Dims, precision, params, tokens, rows):
    pos = jnp.arange(tokens.shape[0])
    emb = params["embed"]["tok"]
    h = emb[tokens].astype(jnp.float32) * m.emb_scale

    def body(h, blk):
        return _layer(m, precision, h, blk, pos), None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    x = _rmsnorm(h[rows], params["final_norm"], m.eps)
    return _linear("pd,vd->pv", x, emb, precision) / m.logit_scale


_forward_jit = jax.jit(_forward, static_argnums=(0, 1))


def logits(config: dict, params, tokens, rows, precision: str = "f32"):
    """Logits (len(rows), vocab) in float32 at the positions ``rows`` of
    one causal pass over ``tokens``; positions after a row do not reach
    it, so ``tokens`` may be padded at the end."""
    if precision not in ("f32", "fp8"):
        raise ValueError(precision)
    with jax.default_matmul_precision("highest"):
        return _forward_jit(dims(config), precision, params,
                            jnp.asarray(tokens, jnp.int32),
                            jnp.asarray(rows, jnp.int32))
