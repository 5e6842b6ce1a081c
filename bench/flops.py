"""Model FLOPs of a Qwen3 decoder, from its configuration's sizes.

One token through the whole model at context position ``p`` (it attends to
``p + 1`` keys) costs, with multiply and add counted as two:

* the linear layers: ``2 * L * (d * (H + 2 KV) * hd + H * hd * d
  + 3 * d * ffn)``;
* attention: ``4 * L * H * hd * (p + 1)`` (scores and the weighted sum);
* the tied unembedding: ``2 * d * vocab``.

Norms, RoPE and softmax are left out, as is the embedding lookup.  Work the
system repeats or throws away (rejected drafts, the drafter and its
training, padding) is not model work and is not counted.
"""
from __future__ import annotations


def linear_flops(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    per_layer = d * (H + 2 * KV) * hd + H * hd * d + 3 * d * c[
        "intermediate_size"]
    return 2 * c["num_hidden_layers"] * per_layer + 2 * d * c["vocab_size"]


def attn_coeff(c: dict) -> int:
    """Attention FLOPs per key attended to, per token."""
    return 4 * c["num_hidden_layers"] * c["num_attention_heads"] * c[
        "head_dim"]


def token_flops(c: dict, pos: int) -> int:
    return linear_flops(c) + attn_coeff(c) * (pos + 1)


def span_flops(c: dict, lo: int, hi: int) -> int:
    """Tokens at positions ``lo .. hi - 1`` each through the whole model."""
    n = max(0, hi - lo)
    keys = (lo + 1 + hi) * n // 2            # sum of (p + 1) over the span
    return linear_flops(c) * n + attn_coeff(c) * keys
