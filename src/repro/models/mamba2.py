"""Mamba2 block (SSD — state-space duality), TPU-adapted.

Training/prefill uses the chunked SSD form: within a chunk, outputs are
dense ``(Q × Q)`` masked matmuls (MXU work, like a tiny attention); across
chunks a compact ``(H, P, N)`` state is propagated by the sequential
recurrence owned by the ``ssd_scan`` Pallas kernel.  Decode is the O(1)
recurrent update.

Sharding: SSD heads are independent → ``ssm_heads → model`` (TP); the
depthwise conv and all projections follow the same split.  The state never
crosses shards.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.dist.logical import constrain
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.models.common import RESIDUAL_AXES, compute_dtype, rmsnorm

__all__ = ["mamba_init", "mamba_apply", "mamba_decode", "mamba_state_init"]


def _dims(cfg: ModelConfig):
    d_inner = cfg.d_inner
    h = cfg.ssm_heads
    p = cfg.ssm_head_dim
    n = cfg.ssm_state
    conv_dim = d_inner + 2 * n            # x, B, C share the conv (n_groups=1)
    return d_inner, h, p, n, conv_dim


def mamba_init(key, cfg: ModelConfig) -> Tuple[Any, Any]:
    d = cfg.d_model
    d_inner, h, p, n, conv_dim = _dims(cfg)
    # in_proj emits [z (d_inner), x (d_inner), B (n), C (n), dt (h)]
    d_proj = 2 * d_inner + 2 * n + h
    ks = jax.random.split(key, 5)
    s = 1.0 / math.sqrt(d)
    params = {
        "in_proj": s * jax.random.normal(ks[0], (d, d_proj), jnp.float32),
        "conv_w": 0.1 * jax.random.normal(ks[1], (conv_dim, cfg.ssm_conv), jnp.float32),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
        "d_skip": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((h,), 1e-2, jnp.float32))),
        "norm_w": jnp.ones((d_inner,), jnp.float32),
        "out_proj": (1.0 / math.sqrt(d_inner))
        * jax.random.normal(ks[2], (d_inner, d), jnp.float32),
    }
    specs = {
        "in_proj": ("embed", "conv_dim"),
        "conv_w": ("conv_dim", None),
        "conv_b": ("conv_dim",),
        "a_log": ("ssm_heads",),
        "d_skip": ("ssm_heads",),
        "dt_bias": ("ssm_heads",),
        "norm_w": ("conv_dim",),
        "out_proj": ("conv_dim", "embed"),
    }
    return params, specs


def _split_proj(cfg: ModelConfig, proj: jax.Array):
    d_inner, h, p, n, _ = _dims(cfg)
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner : 2 * d_inner + 2 * n]
    dt = proj[..., 2 * d_inner + 2 * n :]
    return z, xbc, dt


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv along S: xbc (B, S, C), w (C, K)."""
    k = w.shape[1]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(xbc, dtype=jnp.float32)
    for i in range(k):  # K is tiny (4): static unroll beats conv_general here
        out = out + pad[:, i : i + xbc.shape[1], :].astype(jnp.float32) * w[:, i]
    return jax.nn.silu(out + b).astype(xbc.dtype)


def _segsum_chunk(da: jax.Array):
    """da (B, C, Q, H) → cumulative sums used by the SSD chunk form."""
    cum = jnp.cumsum(da, axis=2)                  # inclusive cumsum over Q
    return cum


def mamba_apply(
    params, cfg: ModelConfig, x: jax.Array, return_state: bool = False
):
    """Full-sequence SSD (train / prefill).  x (B, S, D) → (B, S, D).

    With ``return_state`` also returns the recurrent state after the last
    token ({"ssm", "conv"}) so decode can continue from a prefill."""
    cdt = compute_dtype(cfg)
    b, s_true, d = x.shape
    d_inner, h, p, n, conv_dim = _dims(cfg)
    q = min(cfg.ssm_chunk, s_true)
    pad = (q - s_true % q) % q
    if pad:
        # pad to a chunk multiple; padded steps get dt=0 below, which makes
        # them exact no-ops on the state (decay=e^0=1, contribution=0)
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    s = s_true + pad
    nc = s // q

    proj = x @ params["in_proj"].astype(cdt)
    z, xbc_pre, dt_raw = _split_proj(cfg, proj)
    xbc_pre = constrain(xbc_pre, "batch", "seq", "conv_dim")
    xbc = _causal_conv(xbc_pre, params["conv_w"], params["conv_b"])
    xs = xbc[..., :d_inner].reshape(b, s, h, p)
    bmat = xbc[..., d_inner : d_inner + n]            # (B, S, N)
    cmat = xbc[..., d_inner + n :]                    # (B, S, N)

    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + params["dt_bias"]
    )                                                  # (B, S, H)
    if pad:
        valid = (jnp.arange(s) < s_true)[None, :, None]
        dt = dt * valid  # padded steps: exact state no-ops
    a = -jnp.exp(params["a_log"])                      # (H,) negative
    da = dt * a                                        # (B, S, H) ≤ 0

    # chunk reshape
    xs_c = xs.reshape(b, nc, q, h, p).astype(jnp.float32)
    b_c = bmat.reshape(b, nc, q, n).astype(jnp.float32)
    c_c = cmat.reshape(b, nc, q, n).astype(jnp.float32)
    dt_c = dt.reshape(b, nc, q, h)
    da_c = da.reshape(b, nc, q, h)
    cum = _segsum_chunk(da_c)                          # (B, C, Q, H)

    # intra-chunk (dense, MXU): scores[q_, k_] = C_q·B_k · exp(cum_q - cum_k) · dt_k
    scores = jnp.einsum("bcqn,bckn->bcqk", c_c, b_c)[:, :, None]   # (B,C,1,Q,Q)
    # decay (B, C, H, Q, Q) = exp(cum[q] - cum[k]), causal-masked
    cum_h = jnp.moveaxis(cum, 3, 2)                    # (B, C, H, Q)
    dmat = jnp.exp(cum_h[..., :, None] - cum_h[..., None, :])
    causal = jnp.tril(jnp.ones((q, q), bool))
    dmat = jnp.where(causal, dmat, 0.0)
    dt_h = jnp.moveaxis(dt_c, 3, 2)                    # (B, C, H, Q)
    w = scores * dmat * dt_h[..., None, :]             # (B, C, H, Q, Q)
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", w, xs_c)

    # chunk states: state_c = Σ_k exp(cum_last - cum_k) · dt_k · B_k ⊗ X_k
    last = cum_h[..., -1:]                             # (B, C, H, 1)
    sdecay = jnp.exp(last - cum_h)                     # (B, C, H, Q)
    sw = sdecay * dt_h                                 # (B, C, H, Q)
    states = jnp.einsum("bchk,bckn,bckhp->bchpn", sw, b_c, xs_c)

    # inter-chunk recurrence (Pallas ssd_scan kernel on TPU)
    chunk_decay = jnp.exp(last[..., 0])                # (B, C, H)
    states_bh = (
        states.transpose(0, 2, 1, 3, 4).reshape(b * h, nc, p, n)
    )
    decay_bh = chunk_decay.transpose(0, 2, 1).reshape(b * h, nc)
    prefix = ssd_scan(states_bh, decay_bh)             # (B*H, C, P, N)
    prefix = prefix.reshape(b, h, nc, p, n).transpose(0, 2, 1, 3, 4)

    # inter-chunk output: y_q += (C_q · prefix) * exp(cum_q)
    edecay = jnp.exp(cum_h)                            # (B, C, H, Q)
    y_inter = jnp.einsum(
        "bcqn,bchpn->bcqhp", c_c, prefix
    ) * jnp.moveaxis(edecay, 2, 3)[..., None]
    y = y_intra + y_inter + params["d_skip"][None, None, None, :, None] * xs_c
    y = y.reshape(b, s, d_inner).astype(cdt)

    # gated RMSNorm then out projection
    y = rmsnorm(y * jax.nn.silu(z), params["norm_w"], cfg.norm_eps)
    out = y @ params["out_proj"].astype(cdt)
    if pad:
        out = out[:, :s_true]
    out = checkpoint_name(constrain(out, *RESIDUAL_AXES), "mixer_out")
    if not return_state:
        return out
    # final recurrent state = decay_last * prefix_last + states_last
    # (exact even with padding: padded steps were dt=0 no-ops)
    final = (
        chunk_decay[:, -1][..., None, None] * prefix[:, -1].reshape(b, h, p, n)
        + states[:, -1]
    )
    conv_tail = xbc_pre[:, s_true - (cfg.ssm_conv - 1): s_true, :]
    return out, {"ssm": final, "conv": conv_tail}


def mamba_state_init(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    d_inner, h, p, n, conv_dim = _dims(cfg)
    return {
        "ssm": jnp.zeros((batch, h, p, n), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
    }


def mamba_decode(
    params, cfg: ModelConfig, x: jax.Array, state: Dict[str, jax.Array]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token recurrent step.  x (B, 1, D) → (B, 1, D)."""
    cdt = compute_dtype(cfg)
    b = x.shape[0]
    d_inner, h, p, n, conv_dim = _dims(cfg)
    proj = x[:, 0] @ params["in_proj"].astype(cdt)     # (B, d_proj)
    z, xbc_new, dt_raw = _split_proj(cfg, proj)

    # conv ring: state["conv"] (B, K-1, conv_dim) holds the last K-1 inputs
    conv_in = jnp.concatenate(
        [state["conv"], xbc_new[:, None, :]], axis=1
    )                                                   # (B, K, conv_dim)
    w = params["conv_w"]                                # (conv_dim, K)
    xbc = jnp.einsum("bkc,ck->bc", conv_in.astype(jnp.float32), w)
    xbc = jax.nn.silu(xbc + params["conv_b"]).astype(cdt)
    new_conv = conv_in[:, 1:]

    xs = xbc[:, :d_inner].reshape(b, h, p).astype(jnp.float32)
    bvec = xbc[:, d_inner : d_inner + n].astype(jnp.float32)   # (B, N)
    cvec = xbc[:, d_inner + n :].astype(jnp.float32)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])  # (B, H)
    a = -jnp.exp(params["a_log"])
    decay = jnp.exp(dt * a)                             # (B, H)

    ssm = state["ssm"] * decay[..., None, None] + jnp.einsum(
        "bh,bn,bhp->bhpn", dt, bvec, xs
    )
    y = jnp.einsum("bn,bhpn->bhp", cvec, ssm) + params["d_skip"][None, :, None] * xs
    y = y.reshape(b, d_inner).astype(cdt)
    y = rmsnorm(y * jax.nn.silu(z), params["norm_w"], cfg.norm_eps)
    out = (y @ params["out_proj"].astype(cdt))[:, None, :]
    return out, {"ssm": ssm, "conv": new_conv}
