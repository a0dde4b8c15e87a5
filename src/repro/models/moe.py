"""Mixture-of-Experts layer: expert parallelism via shard_map.

Pattern: activations enter the MoE layer replicated over the
"model" mesh axis (batch sharded over dp); expert weights are sharded
``experts → model`` (+ ``d_model → data`` FSDP).  Because every model shard
sees all (local-batch) tokens, dispatch needs **no all-to-all** — each
shard locally gathers the tokens routed to *its* experts (capacity-bounded
sort-free ranking), runs dense per-expert SwiGLU matmuls, and the combine
is a single ``psum`` over "model" — the same collective a Megatron TP MLP
pays.  FSDP all-gather of expert weights happens inside the shard_map
(gradient becomes psum_scatter under autodiff, i.e. ZeRO semantics).

Capacity: ``C = ceil(T_local · k / E · capacity_factor)`` tokens per
expert; overflow tokens are dropped (switch-style), counted, and exposed
for monitoring.  Aux load-balance loss: ``E · Σ_e f_e · P_e`` (Switch
Transformer) computed on the local shard and psum-averaged over dp.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.logical import current_rules, _current_mesh
from repro.models.common import compute_dtype, dense_init

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg: ModelConfig) -> Tuple[Any, Any]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    params = {
        "router": 0.02 * jax.random.normal(ks[0], (d, e), jnp.float32),
        "wg": s * jax.random.normal(ks[1], (e, d, f), jnp.float32),
        "wu": s * jax.random.normal(ks[2], (e, d, f), jnp.float32),
        "wd": (1.0 / math.sqrt(f)) * jax.random.normal(ks[3], (e, f, d), jnp.float32),
    }
    specs = {
        "router": (None, None),  # replicated: read by every shard every layer
        "wg": ("experts", "embed", "expert_ff"),
        "wu": ("experts", "embed", "expert_ff"),
        "wd": ("experts", "expert_ff", "embed"),
    }
    return params, specs


def _local_moe(
    x_l: jax.Array,        # (B_l, S, D) tokens local to this dp shard
    router: jax.Array,     # (D, E) replicated
    wg: jax.Array,         # (E_l, D, F) local experts (already gathered on D)
    wu: jax.Array,
    wd: jax.Array,
    *,
    cfg: ModelConfig,
    e0,                    # first expert id owned by this shard
    capacity: int,
):
    """Dispatch → per-expert SwiGLU → combine, on one model shard."""
    cdt = compute_dtype(cfg)
    bl, s, d = x_l.shape
    e = cfg.n_experts
    el = wg.shape[0]
    k = cfg.experts_per_token
    t = bl * s
    xf = x_l.reshape(t, d)

    logits = (xf.astype(jnp.float32)) @ router.astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = lax.top_k(probs, k)                               # (T, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)           # renorm

    # --- capacity-bounded ranking (sort-free within expert) --------------
    flat_i = top_i.reshape(-1)                                        # (T*k,)
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    order = jnp.argsort(flat_i, stable=True)
    sorted_i = flat_i[order]
    first = jnp.searchsorted(sorted_i, jnp.arange(e, dtype=sorted_i.dtype))
    rank = jnp.arange(t * k, dtype=jnp.int32) - first[sorted_i].astype(jnp.int32)

    local_e = sorted_i - e0
    keep = (local_e >= 0) & (local_e < el) & (rank < capacity)
    slot_e = jnp.where(keep, local_e, el)            # el = discard row
    slot_c = jnp.where(keep, rank, 0)
    tok_sorted = flat_tok[order]
    w_sorted = flat_w[order]

    tok_buf = jnp.full((el + 1, capacity), t, jnp.int32)             # t = pad row
    tok_buf = tok_buf.at[slot_e, slot_c].set(jnp.where(keep, tok_sorted, t))
    w_buf = jnp.zeros((el + 1, capacity), jnp.float32)
    w_buf = w_buf.at[slot_e, slot_c].set(jnp.where(keep, w_sorted, 0.0))
    tok_buf, w_buf = tok_buf[:el], w_buf[:el]

    xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xe = xpad[tok_buf]                                # (E_l, C, D) gather

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg.astype(cdt)))
    u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(cdt))
    ye = jnp.einsum("ecf,efd->ecd", g * u, wd.astype(cdt))
    ye = ye * w_buf[..., None].astype(cdt)

    y = jnp.zeros((t + 1, d), cdt).at[tok_buf.reshape(-1)].add(
        ye.reshape(-1, d)
    )[:t]

    # --- aux telemetry -----------------------------------------------------
    # Switch load-balance loss on the local token shard (identical on every
    # model shard; dp-mean happens in the caller's loss aggregation).
    counts = jnp.zeros((e,), jnp.float32).at[flat_i].add(1.0)
    dispatch_frac = counts / (t * k)                  # f_e (scatter, no one-hot)
    prob_frac = jnp.mean(probs, axis=0)               # P_e
    aux = e * jnp.sum(dispatch_frac * prob_frac)
    dropped = jnp.sum((~keep) & (local_e >= 0) & (local_e < el))
    return y.reshape(bl, s, d), aux, dropped


def moe_apply(
    params, cfg: ModelConfig, x: jax.Array, no_drop: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, D) → (y (B, S, D), aux_loss scalar).

    ``no_drop=True`` sizes capacity so no token can overflow (worst case:
    every token routes one assignment to the same expert ⇒ C = T).  Used by
    the decode path, where dropping would corrupt generation.
    """
    mesh = _current_mesh()
    b, s, _ = x.shape
    k, e = cfg.experts_per_token, cfg.n_experts

    def cap_for(t_tokens: int) -> int:
        if no_drop:
            return t_tokens
        return max(1, int(cfg.capacity_factor * t_tokens * k / e))

    # Axis resolution comes from the active rule table: "experts" names the
    # expert-parallel axis, "batch"/"embed" the dp/FSDP groups — so
    # `axis_rules` overrides steer the shard_map path like any constrain.
    rules = current_rules()
    names = mesh.axis_names if mesh is not None else ()
    mdl = rules.mesh_axes("experts", names)

    if mesh is None or not isinstance(mdl, str):
        # single-device / no-expert-axis path: all experts local
        t = b * s
        y, aux, _ = _local_moe(
            x, params["router"], params["wg"], params["wu"], params["wd"],
            cfg=cfg, e0=0, capacity=cap_for(t),
        )
        return y, aux

    def _axes(logical):
        got = rules.mesh_axes(logical, names)
        got = () if got is None else ((got,) if isinstance(got, str) else got)
        return tuple(a for a in got if a != mdl)

    dp = _axes("batch")
    n_model = mesh.shape[mdl]
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if e % n_model:
        raise ValueError(f"{e} experts not divisible by model={n_model}")
    el = e // n_model
    if b % n_dp:
        # batch not divisible over dp (e.g. batch=1 long-context decode):
        # keep tokens replicated across dp inside the shard_map
        dp = ()
        n_dp = 1
    t_local = (b // n_dp) * s
    cap = cap_for(t_local)

    # FSDP axes for expert weights (the "embed" rule: pod+data by default)
    fsdp = _axes("embed")
    fsdp_entry = fsdp if len(fsdp) > 1 else (fsdp[0] if fsdp else None)

    def shard_fn(x_l, router, wg_l, wu_l, wd_l):
        # FSDP gather of expert weights over the pod+data axes (ZeRO-3):
        if fsdp:
            wg_f = lax.all_gather(wg_l, fsdp, axis=1, tiled=True)
            wu_f = lax.all_gather(wu_l, fsdp, axis=1, tiled=True)
            wd_f = lax.all_gather(wd_l, fsdp, axis=2, tiled=True)
        else:
            wg_f, wu_f, wd_f = wg_l, wu_l, wd_l
        e0 = lax.axis_index(mdl) * el
        y, aux, dropped = _local_moe(
            x_l, router, wg_f, wu_f, wd_f, cfg=cfg, e0=e0, capacity=cap
        )
        # combine expert contributions across model shards
        y = lax.psum(y, mdl)
        # aux identical across model shards; mean over dp shards
        if dp:
            aux = lax.pmean(aux, dp)
        return y, aux

    batch_axes = dp if dp else None
    in_specs = (
        P(batch_axes, None, None),                # x
        P(None, None),                            # router (replicated)
        P(mdl, fsdp_entry, None),                 # wg (E→model, D→pod+data)
        P(mdl, fsdp_entry, None),                 # wu
        P(mdl, None, fsdp_entry),                 # wd (E→model, F, D→pod+data)
    )
    out_specs = (P(batch_axes, None, None), P())
    smap = partial(
        jax.shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    y, aux = smap(shard_fn)(
        x, params["router"], params["wg"], params["wu"], params["wd"]
    )
    return y, aux
