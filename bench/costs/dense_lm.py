"""Useful FLOPs of a dense decoder step, from the configuration's sizes.

Matmul FLOPs are 2 per weight per token (q, k, v, o projections and the
three SwiGLU matrices of every layer); attention adds 4 * head_dim FLOPs
per head, layer and (query, visible key) pair; the unembedding runs for
the one row whose logits are used (prefill) or for every decoded token.
Recomputation, padding and masked pairs do not count.
"""

from __future__ import annotations


def _per_layer_weights(m: dict) -> int:
    d, h, hkv, f = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff"]
    dh = m.get("head_dim") or d // h
    return d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f


def prefill_flops(m: dict, prompt_tokens: int) -> float:
    """One prompt of ``prompt_tokens`` through every layer, causal."""
    d, h, n = m["d_model"], m["n_heads"], m["n_layers"]
    dh = m.get("head_dim") or d // h
    L = prompt_tokens
    return (2.0 * L * n * _per_layer_weights(m)
            + 4.0 * dh * h * n * L * (L + 1) / 2
            + 2.0 * d * m["vocab_size"])


def decode_flops(m: dict, context: float) -> float:
    """One decoded token that attends to ``context`` positions."""
    d, h, n = m["d_model"], m["n_heads"], m["n_layers"]
    dh = m.get("head_dim") or d // h
    return (2.0 * n * _per_layer_weights(m) + 2.0 * d * m["vocab_size"]
            + 4.0 * dh * h * n * context)
