"""Plain reference of MiniCPM: the equations of ``dense_decoder``, with
the embedding drawn at standard deviation ``0.02 / scale_emb``.

MiniCPM multiplies its embedding by ``scale_emb`` (12) on the way in and
ties it to the output.  Drawn at 0.02, as an unscaled model's embedding
is, the scaled embedding outweighs every layer's contribution to the
residual stream, and the top logit at each position is that position's
own input token (at every position of a 512-token probe at the published
widths on a TPU v5e, by 5.3 standard deviations of the logits at the
median).  The float8 control then chooses the same tokens, and the
comparison that decides ``correct`` could not tell a wrong KV cache
from a right one.
Drawn at ``0.02 / scale_emb`` the stream enters the first layer at 0.02,
as an unscaled model's does, and the output follows the context.  Every
other weight is ``dense_decoder``'s, drawn from the same keys.

:func:`logits` and :func:`dims` are ``dense_decoder``'s.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp


def _load_dense():
    path = Path(__file__).with_name("dense_decoder.py")
    spec = importlib.util.spec_from_file_location("ref_dense_decoder", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dense = _load_dense()
Dims, dims, logits, seed_key = dense.Dims, dense.dims, dense.logits, \
    dense.seed_key

EMBED_STD = 0.02


def _leaf(key, shape, kind, emb_scale):
    if kind == "embed":
        z = jax.random.normal(key, shape, jnp.float32)
        return (EMBED_STD / emb_scale * z).astype(jnp.bfloat16)
    return dense._leaf(key, shape, kind)


@functools.partial(jax.jit, static_argnames=("m",))
def _make(key, *, m: Dims):
    leaves, tree = jax.tree.flatten(dense._shapes(m), is_leaf=dense._is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        _leaf(k, s, kind, m.emb_scale) for k, (s, kind) in zip(keys, leaves)])


def weights(config: dict, seed: int):
    """The served weights, made on the device in one jitted call."""
    return _make(seed_key(seed), m=dims(config))
