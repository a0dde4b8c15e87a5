"""Causal flash attention over one prompt (GQA).

The kernel's operands are ``q (B*Hq, Sq_pad, D)`` and ``k, v (B*Hkv,
Skv_pad, D)``, padded inside the program to the kernel's blocks, and its
output has the shape of ``q``.  The program gives the Mosaic call no name
of its own: called alone it appears as ``flash_attention``, inside a
model's prefill as ``closed_call.N``.  :func:`is_flash` therefore knows
it by that signature; in the LM tier it is the only Pallas kernel.

Useful work is counted from the true prompt lengths, which the padded
operands do not show: ``QK^T`` and ``PV`` over the causal pairs of the
true length, 4 * D FLOPs per pair and query head; bytes: the true q, k,
v read once and the output written once.  Both are lower bounds of what
the kernel must do.
"""

from __future__ import annotations

from typing import Tuple

_WIDTH = {"bf16": 2, "f16": 2, "f32": 4}


def is_flash(kernel) -> bool:
    """A Pallas call with flash attention's operand and output shapes."""
    ops, outs = kernel.operands, kernel.outputs
    if len(ops) != 3 or len(outs) != 1:
        return False
    (qt, q), (kt, k), (vt, v) = ops
    return (len(q) == len(k) == 3 and k == v and outs[0] == (qt, q)
            and qt in _WIDTH and q[0] % k[0] == 0 and q[2] == k[2])


def prompt_cost(m: dict, length: int, dtype: str = "bf16") -> Tuple[float, float]:
    """``(flops, bytes)`` of every layer's flash attention over one prompt
    of ``length`` tokens of the model with sizes ``m``."""
    h, hkv, n = m["n_heads"], m["n_kv_heads"], m["n_layers"]
    d = m.get("head_dim") or m["d_model"] // h
    pairs = length * (length + 1) // 2
    flops = 4.0 * d * h * pairs * n
    moved = float(_WIDTH[dtype] * length * d * (2 * h + 2 * hkv) * n)
    return flops, moved
