"""Plain reference of the yi-6b-full.tp4 configuration: weights and logits.

Written apart from the program and importing nothing of it (a copy of the
plain float32 forward the repository keeps for its tests): a llama-style
decoder — RMSNorm, rotary positions on the two halves of each head,
grouped-query causal softmax attention, SwiGLU MLP — at
``jax.default_matmul_precision("highest")``, no cache, no kernel, no
batching.  One departure from the one-chip file: the layers run as a
``lax.scan`` over the stacked weights, not a Python loop, since the 32
unrolled layers take ~200 s to compile for four chips (the float8
control ~235 s more), which with set-up would not fit a run.

:func:`init_params` makes the weights from the seed in one jitted call,
made where ``shardings`` puts them (split over the four chips: the 24.2 GB
never sit on one), in float32 (the type the program serves them in), laid
out as the program's parameter tree: ``embed.table (V, D)``,
``embed.unembed (D, V)``, ``final_norm (D,)`` and ``blocks`` with a
leading layer axis (``ln1``, ``ln2``, ``attn.wq/wk/wv/wo``,
``mlp.wg/wu/wd``).  Matrices are N(0, 1/fan_in), the embedding and the
unembedding N(0, 0.02^2), norms 1.  The same seed gives the same
weights as the one-chip file's at the same sizes.

:func:`logits` with ``fp8=True`` is the control: every matmul's inputs
rounded to float8 e4m3 (per-row scales for activations, per-tensor for
weights), the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F8_MAX = 448.0  # largest finite float8_e4m3fn


def init_params(m: dict, seed: int, shardings=None):
    """The weights for model sizes ``m`` (the configuration's ``model``),
    made with the output shardings ``shardings`` (a tree like the weights';
    ``None``: on the default device)."""
    d, h, hkv, f, v, n = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                          m["d_ff"], m["vocab_size"], m["n_layers"])
    dh = m.get("head_dim") or d // h

    def normal(key, shape, scale):
        return scale * jax.random.normal(key, shape, jnp.float32)

    def make(key):
        ks = jax.random.split(key, 10)
        lay = lambda k, shape, fan: normal(k, (n,) + shape, fan ** -0.5)  # noqa: E731
        return {
            "embed": {"table": normal(ks[0], (v, d), 0.02),
                      "unembed": normal(ks[1], (d, v), 0.02)},
            "final_norm": jnp.ones((d,), jnp.float32),
            "blocks": {
                "ln1": jnp.ones((n, d), jnp.float32),
                "ln2": jnp.ones((n, d), jnp.float32),
                "attn": {"wq": lay(ks[2], (d, h * dh), d),
                         "wk": lay(ks[3], (d, hkv * dh), d),
                         "wv": lay(ks[4], (d, hkv * dh), d),
                         "wo": lay(ks[5], (h * dh, d), h * dh)},
                "mlp": {"wg": lay(ks[6], (d, f), d),
                        "wu": lay(ks[7], (d, f), d),
                        "wd": lay(ks[8], (f, d), f)},
            },
        }

    make = jax.jit(make) if shardings is None else jax.jit(
        make, out_shardings=shardings)
    return make(jax.random.PRNGKey(seed))


def _f8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    if fp8:
        return _f8(a, -1) @ _f8(w, None)
    return a @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    x1, x2 = x[..., :half], x[..., half:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def logits(params, m: dict, tokens, rows, fp8: bool = False):
    """Logits ``(len(rows), V)`` at positions ``rows`` of one ``(S,)`` token
    sequence (causal: tokens after a row do not change it)."""
    d, h, hkv = m["d_model"], m["n_heads"], m["n_kv_heads"]
    dh = m.get("head_dim") or d // h
    eps, theta = m["norm_eps"], m["rope_theta"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]

    def layer(x, p):
        a = p["attn"]
        y = _rms(x, p["ln1"], eps)
        q = _rope(_mm(y, a["wq"], fp8).reshape(s, h, dh), pos, theta)
        k = _rope(_mm(y, a["wk"], fp8).reshape(s, hkv, dh), pos, theta)
        v = _mm(y, a["wv"], fp8).reshape(s, hkv, dh)
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
        w = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", w, v).reshape(s, h * dh)
        x = x + _mm(o, a["wo"], fp8)
        mm = p["mlp"]
        y = _rms(x, p["ln2"], eps)
        x = x + _mm(jax.nn.silu(_mm(y, mm["wg"], fp8)) * _mm(y, mm["wu"], fp8),
                    mm["wd"], fp8)
        return x, None

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][tokens]
        x, _ = jax.lax.scan(layer, x, params["blocks"])
        x = _rms(x[rows], params["final_norm"], eps)
        return _mm(x, params["embed"]["unembed"], fp8)


def gaps(params, m: dict, tokens, rows, served, fp8_control: bool = False):
    """Per row: how far the reference logit of the ``served`` token lies
    below the reference's best (``fp8_control``: of the token the float8
    control puts first instead)."""
    ref = logits(params, m, tokens, rows)
    best = ref.max(axis=-1)
    if fp8_control:
        served = jnp.argmax(logits(params, m, tokens, rows, fp8=True), axis=-1)
    return best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
