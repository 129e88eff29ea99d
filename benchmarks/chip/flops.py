"""Operations and bytes the served work needs, from the configuration's
published sizes.  Counted as multiply-adds times two; nothing that the
program pads, repeats or throws away counts.

* a prefilled token: every linear layer, attention over its real
  context (the cached prefix plus the chunk up to and including it);
* a decoded token: the same, over its context;
* logits only where a token is sampled: the last prefilled position of
  a request, and every decode step.
"""
from __future__ import annotations


def _sizes(config: dict):
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    kv = config.get("num_key_value_heads", h)
    dh = config.get("head_dim", d // h)
    return d, h, kv, dh, config["intermediate_size"], \
        config["num_hidden_layers"], config["vocab_size"]


def linear_flops_per_token(config: dict) -> float:
    """Q, K, V, O projections and the gated FFN, over all layers."""
    d, h, kv, dh, ff, layers, _ = _sizes(config)
    macs = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * ff
    return 2.0 * macs * layers


def attention_flops(config: dict, cached: int, n: int) -> float:
    """QK^T and PV for ``n`` new tokens after ``cached`` ones: token i
    (0-based) attends to ``cached + i + 1`` keys."""
    d, h, kv, dh, ff, layers, _ = _sizes(config)
    keys = n * cached + n * (n + 1) / 2.0
    return 2.0 * 2.0 * h * dh * keys * layers


def logits_flops(config: dict) -> float:
    d, *_, vocab = _sizes(config)
    return 2.0 * d * vocab


def prefill_flops(config: dict, cached: int, n: int) -> float:
    """A prefill chunk of ``n`` tokens after ``cached``; one sampled
    position (the chunk that completes the prompt)."""
    return (n * linear_flops_per_token(config)
            + attention_flops(config, cached, n) + logits_flops(config))


def decode_flops(config: dict, context: int) -> float:
    """One decoded token whose context holds ``context`` tokens."""
    return (linear_flops_per_token(config)
            + attention_flops(config, context, 1) + logits_flops(config))


def gather_bytes(n_blocks: int, block_tokens: int, row_bytes: int) -> int:
    """One ``kv_layer_gather`` call: each block's rows of one layer read
    from the pool and written to the stream."""
    return 2 * n_blocks * block_tokens * row_bytes


def kv_bytes_per_token(config: dict, dtype_bytes: int = 2) -> int:
    d, h, kv, dh, ff, layers, _ = _sizes(config)
    return 2 * kv * dh * dtype_bytes * layers
