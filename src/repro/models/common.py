"""Shared model substrate: norms, RoPE, GQA attention, SwiGLU MLP,
embeddings and chunked cross-entropy.

Conventions
-----------
* Functional params: nested dicts of jnp arrays.  Every ``init_*`` returns
  ``(params, specs)`` where ``specs`` is a parallel pytree of logical axis
  name tuples (see :mod:`repro.dist.logical`) — the launcher turns specs
  into NamedShardings for pjit.
* Master params are fp32; ``apply`` casts to the compute dtype (bf16).
* Activations are annotated with ``constrain`` at layer boundaries; the
  rule table decides what that means on the current mesh.
* Attention supports three modes: full sequence (train/prefill), one-token
  decode against a contiguous KV cache, and one-token decode against a
  ring-buffer windowed cache (sliding-window layers at long context).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.device import on_tpu
from repro.dist.logical import constrain, current_rules, divisible_spec
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.paged_decode.ops import paged_decode, tiles_fit
from repro.kernels.paged_decode.ref import decode_attention, paged_view

__all__ = [
    "Dtypes",
    "dense_init",
    "rmsnorm_init",
    "rmsnorm",
    "rope_freqs",
    "apply_rope",
    "attend",
    "paged_attend",
    "attention_init",
    "attention_apply",
    "attention_decode",
    "attention_decode_paged",
    "paged_view",
    "paged_write_rows",
    "mlp_init",
    "mlp_apply",
    "embed_init",
    "embed_apply",
    "unembed_logits",
    "last_token_logits",
    "chunked_xent",
    "param_count",
    "RESIDUAL_AXES",
    "REMAT_POLICY",
]

PyTree = Any

# Attention, MLP and mixer outputs are constrained to the sequence-split
# residual layout (Megatron sequence parallelism): GSPMD then lowers the
# tensor-parallel combine as a reduce-scatter, half the wire bytes of an
# all-reduce, and the norms and residual adds between layers run split.
RESIDUAL_AXES = ("batch", "seq_sp", None)

# The layer scans' remat policy: save each layer's attention, MLP and mixer
# outputs (taken after the tensor-parallel combine), so the backward pass
# re-runs neither the forward's combines nor its matmuls, for two
# sequence-split saves a layer.
REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    "attn_out", "ffn_out", "mixer_out"
)


def compute_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, spec, scale: Optional[float] = None):
    """He-style init; returns (param, spec)."""
    if scale is None:
        scale = 1.0 / math.sqrt(shape[0])
    p = scale * jax.random.normal(key, shape, dtype=jnp.float32)
    return p, spec


def rmsnorm_init(d: int):
    return jnp.ones((d,), jnp.float32), ("embed_act",)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (..., S, H, Dh), positions broadcastable to (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., S, 1, Dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attend(
    q: jax.Array,                      # (B, H, Sq, Dh)
    k: jax.Array,                      # (B, Hkv, Skv, Dh)
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """:func:`flash_attention`, laid out on the active mesh.

    The compiler cannot partition a Mosaic kernel, so under a mesh the
    Pallas kernel runs inside ``shard_map``: batch over the data axes and
    heads over the axis the ``"heads"`` rule names, each device attending
    its own heads — the layout ``constrain`` already gives q/k/v.  Query
    heads split contiguously; when the KV heads do not divide that axis
    they are repeated up to the query heads first, so every device holds
    the KV heads its query heads read (GQA group ``h // g``).  Dims the
    mesh does not divide stay replicated.  The XLA path is partitioned
    by the compiler and runs as is.
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    fn = functools.partial(
        flash_attention, causal=causal, window=window,
        use_pallas=use_pallas, interpret=interpret,
    )
    mesh = jax.sharding.get_abstract_mesh()
    if not use_pallas or mesh.empty:
        return fn(q, k, v)
    q_spec = divisible_spec(
        current_rules().spec(("batch", "heads", None, None), mesh),
        q.shape, mesh,
    )
    kv_spec = divisible_spec(q_spec, k.shape, mesh)
    if tuple(kv_spec)[:2] != tuple(q_spec)[:2]:
        g = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        kv_spec = q_spec
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec, check_vma=False,
    )(q, k, v)


def paged_attend(
    q: jax.Array,          # (B, H, Dh)
    k_new: jax.Array,      # (B, Hkv, Dh) the new token's K and V rows
    v_new: jax.Array,
    k_pool: jax.Array,     # (L, Hkv, P, Dh) pools of every layer
    v_pool: jax.Array,
    tables: jax.Array,     # (B, M) int32 block tables
    pos: jax.Array,        # (B,) int32 position of the new token
    layer,                 # int32 scalar: the pools' layer
    block_size: int,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
):
    """Write each slot's new K/V row at ``pos`` into layer ``layer``'s pool,
    then :func:`paged_decode` over positions ``0..pos`` → (k_pool, v_pool,
    context (B, H, Dh) f32), laid out on the active mesh.

    The rows are scattered one (Dh,) row per slot and KV head: a scatter
    whose window is the pool's minor dim leaves the pool in the row-major
    layout the kernel reads (a window over heads and Dh makes the compiler
    relayout the whole pool).  As in :func:`attend`, the Pallas kernel runs
    inside ``shard_map`` under a mesh, and the write with it: query heads
    over the axis the ``"heads"`` rule names, the pool's KV heads over the
    one ``"kv_heads"`` names, so each device writes and attends the KV
    heads it holds, with no collective.  Where the pool is not split like
    the query heads (the KV heads do not divide that axis) the reference
    runs instead, partitioned by the compiler: the pool is too large to
    repeat.
    """
    if use_pallas is None:
        use_pallas = on_tpu() and tiles_fit(
            q.shape[-1], block_size, k_pool.dtype)

    def write_attend(q, k_new, v_new, k_pool, v_pool, tables, pos, layer,
                     use_pallas=use_pallas):
        b, hkv = k_new.shape[:2]
        rows = (tables[jnp.arange(b), pos // block_size] * block_size
                + pos % block_size)[:, None]
        heads = jnp.arange(hkv)[None, :]
        k_pool = k_pool.at[layer, heads, rows, :].set(k_new.astype(k_pool.dtype))
        v_pool = v_pool.at[layer, heads, rows, :].set(v_new.astype(v_pool.dtype))
        ctx = paged_decode(q, k_pool, v_pool, tables, pos + 1, layer,
                           block_size, use_pallas=use_pallas,
                           interpret=interpret)
        return k_pool, v_pool, ctx

    args = (q, k_new, v_new, k_pool, v_pool, tables, pos, layer)
    mesh = jax.sharding.get_abstract_mesh()
    if not use_pallas or mesh.empty:
        return write_attend(*args)
    rules = current_rules()
    row_spec = divisible_spec(rules.spec((None, "heads", None), mesh),
                              q.shape, mesh)
    pool_spec = divisible_spec(rules.spec((None, "kv_heads", None, None), mesh),
                               k_pool.shape, mesh)
    if divisible_spec(row_spec, k_new.shape, mesh) != row_spec or \
            tuple(pool_spec)[1] != tuple(row_spec)[1]:
        return write_attend(*args, use_pallas=False)
    return jax.shard_map(
        write_attend, mesh=mesh,
        in_specs=(row_spec, row_spec, row_spec, pool_spec, pool_spec,
                  P(), P(), P()),
        out_specs=(pool_spec, pool_spec, row_spec), check_vma=False,
    )(*args)


def attention_init(key, cfg: ModelConfig) -> Tuple[PyTree, PyTree]:
    d, h, hkv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    dh = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["wq"], specs["wq"] = dense_init(ks[0], (d, h * dh), ("embed", "heads"))
    params["wk"], specs["wk"] = dense_init(ks[1], (d, hkv * dh), ("embed", "heads"))
    params["wv"], specs["wv"] = dense_init(ks[2], (d, hkv * dh), ("embed", "heads"))
    params["wo"], specs["wo"] = dense_init(ks[3], (h * dh, d), ("heads", "embed"))
    if cfg.qkv_bias:
        params["bq"] = jnp.zeros((h * dh,), jnp.float32)
        params["bk"] = jnp.zeros((hkv * dh,), jnp.float32)
        params["bv"] = jnp.zeros((hkv * dh,), jnp.float32)
        specs["bq"] = ("heads",)
        specs["bk"] = ("heads",)
        specs["bv"] = ("heads",)
    return params, specs


def _qkv(params, cfg: ModelConfig, x: jax.Array):
    """x (B, S, D) -> q (B,S,H,Dh), k/v (B,S,Hkv,Dh) in compute dtype."""
    cdt = compute_dtype(cfg)
    b, s, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = x @ params["wq"].astype(cdt)
    k = x @ params["wk"].astype(cdt)
    v = x @ params["wv"].astype(cdt)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(cdt)
        k = k + params["bk"].astype(cdt)
        v = v + params["bv"].astype(cdt)
    return (
        q.reshape(b, s, h, dh),
        k.reshape(b, s, hkv, dh),
        v.reshape(b, s, hkv, dh),
    )


def attention_apply(
    params,
    cfg: ModelConfig,
    x: jax.Array,                      # (B, S, D)
    positions: jax.Array,              # (S,) or (B, S)
    causal: bool = True,
    window: Optional[int] = None,
    use_rope: bool = True,
    kv_from: Optional[jax.Array] = None,  # cross-attention source (B, F, D)
) -> jax.Array:
    """Full-sequence attention (train / prefill / cross)."""
    cdt = compute_dtype(cfg)
    b, s, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    if kv_from is None:
        q, k, v = _qkv(params, cfg, x)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    else:
        # cross attention: q from x, k/v from encoder output (no RoPE)
        f = kv_from.shape[1]
        q = (x @ params["wq"].astype(cdt)).reshape(b, s, h, dh)
        k = (kv_from @ params["wk"].astype(cdt)).reshape(b, f, hkv, dh)
        v = (kv_from @ params["wv"].astype(cdt)).reshape(b, f, hkv, dh)
        causal = False
        window = None
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "heads", None)
    v = constrain(v, "batch", "seq", "heads", None)
    out = attend(
        jnp.swapaxes(q, 1, 2),
        jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2),
        causal=causal,
        window=window,
    )                                              # (B, H, S, Dh)
    out = jnp.swapaxes(out, 1, 2).reshape(b, s, h * dh)
    out = out @ params["wo"].astype(cdt)
    out = constrain(out, *RESIDUAL_AXES)
    return checkpoint_name(out, "attn_out")


def attention_decode(
    params,
    cfg: ModelConfig,
    x: jax.Array,                 # (B, 1, D)
    pos: jax.Array,               # (B,) absolute position of the new token
    cache: Dict[str, jax.Array],  # {"k","v"}: (B, Hkv, S_slots, Dh)
    window: Optional[int] = None,
    use_rope: bool = True,
    update_cache: bool = True,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode.  Contiguous cache when ``window is None`` (slot =
    absolute position); ring-buffer cache otherwise (slot = pos % window)."""
    cdt = compute_dtype(cfg)
    b, _, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = _qkv(params, cfg, x)            # (B,1,H,Dh)/(B,1,Hkv,Dh)
    if use_rope:
        p1 = pos[:, None]
        q = apply_rope(q, p1, cfg.rope_theta)
        k = apply_rope(k, p1, cfg.rope_theta)
    q = q[:, 0]                                # (B, H, Dh)
    k_new = jnp.swapaxes(k, 1, 2)              # (B, Hkv, 1, Dh)
    v_new = jnp.swapaxes(v, 1, 2)

    slots = cache["k"].shape[2]
    slot = pos % window if window is not None else pos

    if update_cache:
        def upd(c, n, s_):
            return lax.dynamic_update_slice(c, n.astype(c.dtype), (0, s_, 0))

        k_cache = jax.vmap(upd)(cache["k"], k_new, slot)
        v_cache = jax.vmap(upd)(cache["v"], v_new, slot)
    else:
        k_cache, v_cache = cache["k"], cache["v"]

    idx = jnp.arange(slots)[None, :]           # (1, S_slots)
    if window is None:
        valid = idx <= pos[:, None]
    else:
        # ring buffer: slot s holds token t = pos - ((pos - s) mod W)
        t = pos[:, None] - (pos[:, None] - idx) % window
        valid = t >= 0
    # unchunked on purpose: under a (batch, seq -> model) cache sharding a
    # chunk reshape would reshard the cache every layer, while this
    # einsum + softmax is GSPMD's flash-decoding pattern (per-shard partial
    # softmax and scalar combines)
    ctx = decode_attention(q, k_cache, v_cache, valid)  # (B,Hkv,G,Dh)
    ctx = ctx.reshape(b, h * dh).astype(cdt)
    out = (ctx @ params["wo"].astype(cdt))[:, None, :]  # (B,1,D)
    return out, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# paged (block) KV cache
# ---------------------------------------------------------------------------
#
# The paged cache replaces the per-sequence contiguous (B, Hkv, S, Dh)
# cache with ONE preallocated pool of fixed-size blocks shared by every
# batch slot: pool (Hkv, P, Dh) where P = n_blocks * block_size and block
# i owns rows [i*bs, (i+1)*bs).  A per-slot block table (B, M) of block
# ids maps logical token position t to pool row
# ``table[b, t // bs] * bs + t % bs``.  All shapes are static (fixed pool,
# fixed table width), so decode traces once and slot admission/eviction
# never retraces — the whole point for continuous batching.  Block id 0
# is reserved as a trash block: unallocated table entries point at it, so
# writes from inactive slots land somewhere harmless and reads from it
# are always masked by the position-validity mask.


def paged_write_rows(
    pool: jax.Array,        # (Hkv, P, Dh)
    rows: jax.Array,        # (Hkv, S, Dh) values for logical positions start..start+S-1
    table_row: jax.Array,   # (M,) int32 block table of the target slot
    block_size: int,
    start: int = 0,
) -> jax.Array:
    """Scatter S contiguous logical positions of one slot into the pool
    (prefill → paged cache hand-off).  ``start`` offsets the logical
    positions — suffix prefill writes rows start..start+S-1 after adopted
    prefix blocks, leaving those untouched.  Positions past the slot's
    allocated blocks resolve to the trash block."""
    s = rows.shape[1]
    t = start + jnp.arange(s)
    flat = table_row[t // block_size] * block_size + t % block_size
    return pool.at[:, flat, :].set(rows.astype(pool.dtype))


def attention_decode_paged(
    params,
    cfg: ModelConfig,
    x: jax.Array,                 # (B, 1, D)
    pos: jax.Array,               # (B,) absolute position of the new token
    cache: Dict[str, jax.Array],  # {"k","v"}: (L, Hkv, P, Dh) pools of every layer
    tables: jax.Array,            # (B, M) int32 block tables
    block_size: int,
    layer,                        # int32 scalar: this layer's pool
    use_rope: bool = True,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against the paged pool of layer ``layer``.

    The new token's K/V goes to its slot's block at ``pos`` (a scatter of
    B rows into the pools of every layer, in place when the caller carries
    them), then each slot attends its first ``pos + 1`` positions where
    they lie in the pool (:func:`paged_attend`): the kernel reads only
    the blocks below that length, and the reference gathers the slot's
    whole table width.
    """
    cdt = compute_dtype(cfg)
    b, _, d = x.shape
    h, dh = cfg.n_heads, cfg.resolved_head_dim
    q, k, v = _qkv(params, cfg, x)            # (B,1,H,Dh)/(B,1,Hkv,Dh)
    if use_rope:
        p1 = pos[:, None]
        q = apply_rope(q, p1, cfg.rope_theta)
        k = apply_rope(k, p1, cfg.rope_theta)
    k_pool, v_pool, ctx = paged_attend(
        q[:, 0], k[:, 0], v[:, 0], cache["k"], cache["v"], tables, pos,
        layer, block_size,
    )                                          # ctx (B, H, Dh) f32
    ctx = ctx.reshape(b, h * dh).astype(cdt)
    out = (ctx @ params["wo"].astype(cdt))[:, None, :]  # (B,1,D)
    return out, {"k": k_pool, "v": v_pool}


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    params = {}
    specs = {}
    params["wg"], specs["wg"] = dense_init(ks[0], (d, f), ("embed", "d_ff"))
    params["wu"], specs["wu"] = dense_init(ks[1], (d, f), ("embed", "d_ff"))
    params["wd"], specs["wd"] = dense_init(ks[2], (f, d), ("d_ff", "embed"))
    return params, specs


def mlp_apply(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    cdt = compute_dtype(cfg)
    g = jax.nn.silu(x @ params["wg"].astype(cdt))
    u = x @ params["wu"].astype(cdt)
    h = constrain(g * u, "batch", "seq", "d_ff")
    out = h @ params["wd"].astype(cdt)
    out = constrain(out, *RESIDUAL_AXES)
    return checkpoint_name(out, "ffn_out")


# ---------------------------------------------------------------------------
# embedding / unembedding / loss
# ---------------------------------------------------------------------------

def embed_init(key, cfg: ModelConfig):
    v, d = cfg.vocab_size, cfg.d_model
    ks = jax.random.split(key, 2)
    params = {"table": 0.02 * jax.random.normal(ks[0], (v, d), jnp.float32)}
    specs = {"table": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        params["unembed"], specs["unembed"] = dense_init(
            ks[1], (d, v), ("embed", "vocab"), scale=0.02
        )
    return params, specs


def _vocab_axis(mesh, vocab: int) -> Optional[str]:
    """The mesh axis the vocabulary is split over, on a tensor-parallel
    mesh (every other axis of size 1) that divides it; else None."""
    if mesh.empty:
        return None
    axis = current_rules().mesh_axes("vocab", mesh.axis_names)
    if not isinstance(axis, str) or mesh.shape[axis] == 1:
        return None
    if vocab % mesh.shape[axis] or any(
            n > 1 for a, n in mesh.shape.items() if a != axis):
        return None
    return axis


def embed_apply(params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    cdt = compute_dtype(cfg)
    mesh = jax.sharding.get_abstract_mesh()
    axis = _vocab_axis(mesh, cfg.vocab_size)
    if axis is not None:
        # Tensor parallel: each device looks the tokens up in its own
        # vocabulary rows (zeros for tokens it does not hold), and one
        # all-reduce of the (B, S, D) rows assembles them — the table never
        # moves.  Exactly one device contributes each row, so the sum is
        # the row itself.
        table_spec = divisible_spec(
            current_rules().spec(("vocab", "embed"), mesh),
            params["table"].shape, mesh,
        )

        def lookup(table, ids):
            n = table.shape[0]
            local = ids - lax.axis_index(axis) * n
            hit = (local >= 0) & (local < n)
            rows = table[jnp.where(hit, local, 0)].astype(cdt)
            return lax.psum(jnp.where(hit[..., None], rows, 0), axis)

        x = jax.shard_map(
            lookup, mesh=mesh, in_specs=(table_spec, P()), out_specs=P(),
            check_vma=False,
        )(params["table"], tokens)
        return constrain(x, "batch", "seq", None)
    # Relayout the table for the lookup: vocab-replicated, d_model sharded
    # over the FSDP axes.  Gathering straight from the (vocab→model,
    # d→fsdp) training layout makes SPMD "involuntarily fully rematerialize"
    # the gathered activations (XLA b/433785288); one explicit all-gather of
    # the (small) table shard is strictly cheaper.  §Perf iteration.
    table = constrain(params["table"].astype(cdt), None, "embed")
    x = table[tokens]
    return constrain(x, "batch", "seq", None)


def unembed_logits(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    cdt = compute_dtype(cfg)
    w = (
        params["table"].T if cfg.tie_embeddings else params["unembed"]
    ).astype(cdt)
    logits = x @ w
    return constrain(logits, "batch", "seq", "vocab")


def last_token_logits(
    params,
    cfg: ModelConfig,
    hidden: jax.Array,                    # (B, S, D) final hidden states
    lengths: Optional[jax.Array] = None,  # (B,) true prompt lengths
    offset: int = 0,                      # prepended non-text positions (VLM)
) -> jax.Array:
    """Logits at each sequence's TRUE last prompt position.

    Right-padded ragged batches must not read their "last logits" from a
    pad row — gather hidden at ``offset + lengths - 1`` per sequence.
    ``lengths=None`` keeps the uniform-batch fast path (last row).
    """
    if lengths is None:
        last = hidden[:, -1:, :]
    else:
        idx = (lengths.astype(jnp.int32) + offset - 1)[:, None, None]
        last = jnp.take_along_axis(hidden, idx, axis=1)
    return unembed_logits(params, cfg, last)[:, 0]


def chunked_xent(
    params,
    cfg: ModelConfig,
    hidden: jax.Array,     # (B, S, D) final hidden states
    targets: jax.Array,    # (B, S) next-token ids
    mask: Optional[jax.Array] = None,   # (B, S) 1 = contributes to loss
    chunk: int = 512,
) -> jax.Array:
    """Cross-entropy without materializing (B, S, V) logits at once.

    lax.map over sequence chunks: each step computes a (B, chunk, V) logits
    slab (vocab-sharded over "model"), its logsumexp, and the target logit.
    Peak logits memory drops S/chunk-fold — required at 262k vocab.
    """
    b, s, d = hidden.shape
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    mask = mask.astype(jnp.float32)
    c = min(chunk, s)
    n_chunks = (s + c - 1) // c
    pad = n_chunks * c - s
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hs = hidden.reshape(b, n_chunks, c, d).swapaxes(0, 1)   # (n, B, c, D)
    ts = targets.reshape(b, n_chunks, c).swapaxes(0, 1)
    ms = mask.reshape(b, n_chunks, c).swapaxes(0, 1)

    def one(args):
        hx, tx, mx = args
        logits = unembed_logits(params, cfg, hx).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)             # (B, c)
        tgt = jnp.take_along_axis(logits, tx[..., None], axis=-1)[..., 0]
        nll = (lse - tgt) * mx
        return jnp.sum(nll)

    losses = jnp.sum(lax.map(one, (hs, ts, ms)))
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return losses / denom


def param_count(params: PyTree) -> int:
    return int(
        sum(x.size for x in jax.tree_util.tree_leaves(params))
    )
