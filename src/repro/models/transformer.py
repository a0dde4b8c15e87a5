"""Decoder-only LM: dense / local-global (gemma3) / MoE / VLM families.

Layer stacks are ``lax.scan`` over stacked weights (the HLO, and so the
compile time, does not grow with depth).  Uniform archs scan over single
layers; gemma3 scans over blocks of ``local_block`` layers (5
sliding-window + 1 global).  Remat wraps the scanned body and saves only
each layer's attention and MLP outputs
(:data:`~repro.models.common.REMAT_POLICY`); the carried residual stream
is sequence-sharded over "model" (logical axis ``seq_sp``) so the saved
activations per chip stay small.

Entry points: ``init_lm``, ``lm_loss`` (train), ``lm_prefill`` (forward +
KV cache build), ``lm_decode_step`` (one-token serve), ``lm_cache_init``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.dist.logical import axis_rules, constrain
from repro.models import moe as moe_mod
from repro.models.common import (
    REMAT_POLICY,
    RESIDUAL_AXES,
    attend,
    attention_apply,
    attention_decode,
    attention_decode_paged,
    attention_init,
    chunked_xent,
    compute_dtype,
    embed_apply,
    embed_init,
    last_token_logits,
    mlp_apply,
    mlp_init,
    paged_view,
    paged_write_rows,
    rmsnorm,
    rmsnorm_init,
    unembed_logits,
    _qkv,
    apply_rope,
)

__all__ = [
    "init_lm",
    "lm_forward",
    "lm_loss",
    "lm_prefill",
    "lm_decode_step",
    "lm_cache_init",
    "lm_paged_cache_init",
    "lm_decode_step_paged",
    "lm_paged_prefill_write",
    "lm_prefill_suffix",
    "layer_windows",
]

PyTree = Any


def _n_scan(cfg: ModelConfig) -> Tuple[int, int]:
    """(number of scan steps, layers per step)."""
    if cfg.local_block:
        assert cfg.n_layers % cfg.local_block == 0
        return cfg.n_layers // cfg.local_block, cfg.local_block
    return cfg.n_layers, 1


def layer_windows(cfg: ModelConfig):
    """Window (or None) per sub-layer position within one scan step."""
    _, per = _n_scan(cfg)
    if cfg.local_block:
        # gemma3: positions 0..per-2 local (sliding window), last one global
        return [cfg.window] * (per - 1) + [None]
    return [cfg.window] * per


def _is_moe_layer(cfg: ModelConfig) -> bool:
    return cfg.n_experts > 0 and cfg.family in ("moe",)


def _sublayer_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {}
    s: Dict[str, Any] = {}
    p["ln1"], s["ln1"] = rmsnorm_init(cfg.d_model)
    p["attn"], s["attn"] = attention_init(ks[0], cfg)
    p["ln2"], s["ln2"] = rmsnorm_init(cfg.d_model)
    if _is_moe_layer(cfg):
        p["moe"], s["moe"] = moe_mod.moe_init(ks[1], cfg)
    else:
        p["mlp"], s["mlp"] = mlp_init(ks[1], cfg)
    return p, s


def _stack_inits(init_fn, key, n: int):
    """vmap an init over n keys; returns stacked params + per-layer specs."""
    keys = jax.random.split(key, n)
    params = jax.vmap(lambda k: init_fn(k)[0])(keys)
    _, specs = init_fn(key)  # structure only
    specs = jax.tree_util.tree_map(
        lambda sp: ("layers",) + tuple(sp),
        specs,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    return params, specs


def init_lm(cfg: ModelConfig, key) -> Tuple[PyTree, PyTree]:
    n_steps, per = _n_scan(cfg)
    ks = jax.random.split(key, 3)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["embed"], specs["embed"] = embed_init(ks[0], cfg)

    if per == 1:
        blk_p, blk_s = _stack_inits(lambda k: _sublayer_init(k, cfg), ks[1], n_steps)
    else:
        def block_init(k):
            kk = jax.random.split(k, per)
            ps, ss = zip(*[_sublayer_init(kk[i], cfg) for i in range(per)])
            stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ps)
            return stacked, jax.tree_util.tree_map(
                lambda sp: ("block_pos",) + tuple(sp),
                ss[0],
                is_leaf=lambda x: isinstance(x, tuple),
            )
        blk_p, blk_s = _stack_inits(block_init, ks[1], n_steps)
    params["blocks"] = blk_p
    specs["blocks"] = blk_s
    params["final_norm"], specs["final_norm"] = rmsnorm_init(cfg.d_model)
    return params, specs


def _apply_sublayer(p, cfg: ModelConfig, x, positions, window):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    x = x + attention_apply(p["attn"], cfg, h, positions, causal=True, window=window)
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        y, aux = moe_mod.moe_apply(p["moe"], cfg, h)
    else:
        y, aux = mlp_apply(p["mlp"], cfg, h), jnp.zeros((), jnp.float32)
    return x + y, aux


def lm_forward(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,                      # (B, S_txt)
    extra_embeds: Optional[jax.Array] = None,  # (B, I, D) VLM patch embeds
) -> Tuple[jax.Array, jax.Array]:
    """→ (hidden (B, S, D), aux_loss scalar)."""
    cdt = compute_dtype(cfg)
    x = embed_apply(params["embed"], cfg, tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(cdt), x], axis=1)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]
    windows = layer_windows(cfg)
    per = len(windows)

    def body(carry, blk):
        x, aux = carry
        x = constrain(x, "batch", "seq_sp", None)
        if per == 1:
            x, a = _apply_sublayer(blk, cfg, x, positions, windows[0])
            aux = aux + a
        else:
            for i in range(per):
                sub = jax.tree_util.tree_map(lambda v: v[i], blk)
                x, a = _apply_sublayer(sub, cfg, x, positions, windows[i])
                aux = aux + a
        x = constrain(x, "batch", "seq_sp", None)
        return (x, aux), None

    body = jax.checkpoint(body, policy=REMAT_POLICY)
    (x, aux), _ = lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["blocks"],
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return constrain(x, "batch", "seq", None), aux


def lm_loss(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,                       # (B, S_txt)
    loss_mask: Optional[jax.Array] = None,   # (B, S_txt)
    extra_embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross entropy (+ router aux loss)."""
    hidden, aux = lm_forward(params, cfg, tokens, extra_embeds)
    n_img = 0 if extra_embeds is None else extra_embeds.shape[1]
    t = tokens.shape[1]
    if n_img:
        # hidden[I-1 .. I+T-2] predicts tokens[0 .. T-1]
        pred = lax.dynamic_slice_in_dim(hidden, n_img - 1, t, axis=1)
        targets = tokens
        mask = loss_mask
    else:
        pred = hidden[:, :-1]
        targets = tokens[:, 1:]
        mask = None if loss_mask is None else loss_mask[:, 1:]
    xent = chunked_xent(params["embed"], cfg, pred, targets, mask)
    loss = xent + cfg.router_aux_coef * aux
    return loss, {"xent": xent, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def lm_cache_init(cfg: ModelConfig, batch: int, max_len: int):
    """Stacked per-scan-step KV caches (+ logical specs)."""
    n_steps, per = _n_scan(cfg)
    hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    cdt = compute_dtype(cfg)
    windows = layer_windows(cfg)

    def slot_count(w):
        return min(w, max_len) if w is not None else max_len

    caches = []
    for i in range(per):
        sl = slot_count(windows[i])
        kv = {
            "k": jnp.zeros((n_steps, batch, hkv, sl, dh), cdt),
            "v": jnp.zeros((n_steps, batch, hkv, sl, dh), cdt),
        }
        caches.append(kv)
    cache = {f"pos{i}": c for i, c in enumerate(caches)}
    spec = jax.tree_util.tree_map(
        lambda _: ("layers", "batch", "kv_heads", None, None), cache
    )
    return cache, spec


def _whole_sequence(fn):
    """Serving prefill keeps the residual whole along the sequence (no
    sequence parallelism): a layer's tensor-parallel output is combined by
    one all-reduce, q/k/v and the cache come out split by head as the
    weights and the paged pool are, and a prompt length the model axis
    does not split evenly needs no halo exchange.  (With the residual split
    by sequence, the TPU compiler's partitioner overflowed its stack on a
    windowed matmul of a 32-layer prefill over four chips.)"""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with axis_rules({"seq_sp": None}):
            return fn(*args, **kwargs)

    return wrapped


@_whole_sequence
def lm_prefill(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,
    extra_embeds: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
    lengths: Optional[jax.Array] = None,
) -> Tuple[jax.Array, PyTree]:
    """Full-sequence forward that also materializes the KV cache.

    Returns (last-token logits (B, V), cache).  Window layers keep only the
    trailing ``window`` keys (ring-buffer layout, slot = pos % window).
    ``lengths`` (B,) gathers each sequence's true last-prompt-position
    logits so right-padded ragged batches don't read a pad row.
    """
    cdt = compute_dtype(cfg)
    x = embed_apply(params["embed"], cfg, tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(cdt), x], axis=1)
    b, s, _ = x.shape
    max_len = max(max_len or s, s)
    positions = jnp.arange(s)[None, :]
    windows = layer_windows(cfg)
    per = len(windows)

    def sub_with_cache(p, x, window):

        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _qkv(p["attn"], cfg, h)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        kc = jnp.swapaxes(k, 1, 2)                   # (B, Hkv, S, Dh)
        vc = jnp.swapaxes(v, 1, 2)
        # cache layout (k/v computed once, reused for attention below)
        if window is not None and s >= window:
            # ring layout: slot = pos % window over the last `window` tokens
            start = s - window
            roll = s % window
            kv = {
                "k": jnp.roll(kc[:, :, start:], shift=roll, axis=2).astype(cdt),
                "v": jnp.roll(vc[:, :, start:], shift=roll, axis=2).astype(cdt),
            }
        else:
            pad = max_len if window is None else min(window, max_len)
            kv = {
                "k": jnp.pad(kc, ((0, 0), (0, 0), (0, pad - s), (0, 0))).astype(cdt),
                "v": jnp.pad(vc, ((0, 0), (0, 0), (0, pad - s), (0, 0))).astype(cdt),
            }
        attn = attend(
            jnp.swapaxes(q, 1, 2), kc, vc, causal=True, window=window
        )
        attn = jnp.swapaxes(attn, 1, 2).reshape(x.shape[0], s, -1)
        x = x + constrain(
            attn @ p["attn"]["wo"].astype(cdt), *RESIDUAL_AXES
        )
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if "moe" in p:
            y, _ = moe_mod.moe_apply(p["moe"], cfg, h2)
        else:
            y = mlp_apply(p["mlp"], cfg, h2)
        return x + y, kv

    def body(carry, blk):
        x = carry
        x = constrain(x, "batch", "seq_sp", None)
        kvs = {}
        if per == 1:
            x, kv = sub_with_cache(blk, x, windows[0])
            kvs["pos0"] = kv
        else:
            for i in range(per):
                sub = jax.tree_util.tree_map(lambda v: v[i], blk)
                x, kv = sub_with_cache(sub, x, windows[i])
                kvs[f"pos{i}"] = kv
        return x, kvs

    x, cache = lax.scan(body, x, params["blocks"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    offset = extra_embeds.shape[1] if extra_embeds is not None else 0
    logits = last_token_logits(
        params["embed"], cfg, x, lengths=lengths, offset=offset
    )
    return logits, cache


def lm_decode_step(
    params,
    cfg: ModelConfig,
    token: jax.Array,        # (B, 1) int32
    pos: jax.Array,          # (B,) absolute position of `token`
    cache: PyTree,
) -> Tuple[jax.Array, PyTree]:
    """One-token decode through the scanned stack.  → (logits (B,V), cache)."""
    x = embed_apply(params["embed"], cfg, token)
    windows = layer_windows(cfg)
    per = len(windows)

    def sub_decode(p, x, kv, window):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        attn, kv = attention_decode(p["attn"], cfg, h, pos, kv, window=window)
        x = x + attn
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if "moe" in p:
            y, _ = moe_mod.moe_apply(p["moe"], cfg, h, no_drop=True)
        else:
            y = mlp_apply(p["mlp"], cfg, h)
        return x + y, kv

    def body(x, xs):
        blk, kvs = xs
        new_kvs = {}
        if per == 1:
            x, kv = sub_decode(blk, x, kvs["pos0"], windows[0])
            new_kvs["pos0"] = kv
        else:
            for i in range(per):
                sub = jax.tree_util.tree_map(lambda v: v[i], blk)
                x, kv = sub_decode(sub, x, kvs[f"pos{i}"], windows[i])
                new_kvs[f"pos{i}"] = kv
        return x, new_kvs

    x, new_cache = lax.scan(body, x, (params["blocks"], cache))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed_logits(params["embed"], cfg, x)[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# serving: paged (block) KV cache
# ---------------------------------------------------------------------------

def _require_no_windows(cfg: ModelConfig) -> None:
    if any(w is not None for w in layer_windows(cfg)):
        raise NotImplementedError(
            "paged KV cache covers global-attention layers only; "
            f"{cfg.name} has sliding-window layers (window={cfg.window}, "
            f"local_block={cfg.local_block}) — serve it with the static "
            "engine, or page only the global layers (open follow-up)"
        )


def lm_paged_cache_init(cfg: ModelConfig, n_blocks: int, block_size: int):
    """One shared block pool per scan position (+ logical specs).

    Pool layout (n_steps, Hkv, n_blocks * block_size, Dh): block i owns
    rows [i*bs, (i+1)*bs); block 0 is the trash block (see
    :mod:`repro.serve.kvcache`).  Unlike ``lm_cache_init`` there is no
    batch dimension — slots share the pool through their block tables, so
    HBM is sized to the workload's live tokens, not slots × max_len.
    """
    _require_no_windows(cfg)
    n_steps, per = _n_scan(cfg)
    hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    cdt = compute_dtype(cfg)
    cache = {
        f"pos{i}": {
            "k": jnp.zeros((n_steps, hkv, n_blocks * block_size, dh), cdt),
            "v": jnp.zeros((n_steps, hkv, n_blocks * block_size, dh), cdt),
        }
        for i in range(per)
    }
    spec = jax.tree_util.tree_map(
        lambda _: ("layers", "kv_heads", None, None), cache
    )
    return cache, spec


def lm_decode_step_paged(
    params,
    cfg: ModelConfig,
    token: jax.Array,        # (B, 1) int32
    pos: jax.Array,          # (B,) absolute position of `token`
    tables: jax.Array,       # (B, M) int32 per-slot block tables
    cache: PyTree,           # lm_paged_cache_init layout
    block_size: int,
) -> Tuple[jax.Array, PyTree]:
    """One-token decode against the shared block pool.  → (logits, cache).

    The pools ride the layer scan as its carry, whole: each layer scatters
    its B new rows into them in place and attends layer ``l``'s rows
    where they lie, so no layer slices out or stacks back a pool-sized
    copy."""
    _require_no_windows(cfg)
    x = embed_apply(params["embed"], cfg, token)
    n_steps, per = _n_scan(cfg)

    def sub_decode(p, x, kv, layer):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        attn, kv = attention_decode_paged(
            p["attn"], cfg, h, pos, kv, tables, block_size, layer
        )
        x = x + attn
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if "moe" in p:
            y, _ = moe_mod.moe_apply(p["moe"], cfg, h, no_drop=True)
        else:
            y = mlp_apply(p["mlp"], cfg, h)
        return x + y, kv

    def body(carry, xs):
        x, kvs = carry
        blk, layer = xs
        kvs = dict(kvs)
        if per == 1:
            x, kvs["pos0"] = sub_decode(blk, x, kvs["pos0"], layer)
        else:
            for i in range(per):
                sub = jax.tree_util.tree_map(lambda v: v[i], blk)
                x, kvs[f"pos{i}"] = sub_decode(sub, x, kvs[f"pos{i}"], layer)
        return (x, kvs), None

    (x, new_cache), _ = lax.scan(
        body, (x, cache), (params["blocks"], jnp.arange(n_steps)),
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed_logits(params["embed"], cfg, x)[:, 0]
    return logits, new_cache


def lm_paged_prefill_write(
    cfg: ModelConfig,
    cache: PyTree,           # lm_paged_cache_init layout
    prefill_cache: PyTree,   # lm_cache_init layout, batch dim of 1
    table_row: jax.Array,    # (M,) int32 block table of the admitted slot
    block_size: int,
    start: int = 0,
) -> PyTree:
    """Scatter one prefilled sequence's dense KV rows into the pool.

    ``prefill_cache`` is what ``lm_prefill(..., max_len=bucket)`` built for
    a batch of one; its ``bucket`` rows land at the slot's block-table
    positions from logical position ``start`` on (rows past the allocated
    blocks resolve to the trash block, and pad rows inside them are masked
    until decode overwrites).  A non-zero ``start`` leaves the adopted
    prefix blocks untouched (prefix-cache suffix hand-off).
    """
    _require_no_windows(cfg)

    def write(pool, dense):
        # pool (n_steps, Hkv, P, Dh); dense (n_steps, 1, Hkv, S, Dh)
        return jax.vmap(
            lambda pl, dn: paged_write_rows(
                pl, dn, table_row, block_size, start=start
            )
        )(pool, dense[:, 0])

    return jax.tree_util.tree_map(write, cache, prefill_cache)


@_whole_sequence
def lm_prefill_suffix(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,       # (1, S_suf) suffix tokens, padded to a block multiple
    start: int,              # static: adopted prefix length, multiple of block_size
    table_row: jax.Array,    # (M,) int32 block table of the admitted slot
    cache: PyTree,           # lm_paged_cache_init layout
    block_size: int,
    lengths: Optional[jax.Array] = None,  # (1,) true suffix length
) -> Tuple[jax.Array, PyTree]:
    """Prefill only a prompt's suffix against adopted prefix blocks.

    The slot's first ``start`` logical positions already hold the prefix
    KV (adopted, refcounted, from a :class:`repro.serve.kvcache.PrefixIndex`
    hit); this pass embeds just the suffix at positions
    ``start..start+S-1``, writes its K/V into the pool per layer, and runs
    flash attention with the gathered ``start + S`` keys — so suffix
    queries attend to the adopted blocks exactly as full prefill's rows
    ``start..`` attend to its recomputed prefix.

    Bitwise parity with :func:`lm_prefill` holds because the key-axis
    length matches (full bucket ``blocks_for(L)*bs == start + S`` when
    ``start ≡ 0 (mod bs)``), the same flash kernel sees the same per-row
    causal masks, masked positions contribute exact zeros, and the pool
    round-trip is dtype-identity (KV is computed in the cache dtype).
    Asserted by tests, and the basis of the engine's prefix-on vs
    prefix-off byte parity.
    """
    _require_no_windows(cfg)
    s = tokens.shape[1]
    if start % block_size != 0:
        raise ValueError(f"start {start} not a multiple of block_size {block_size}")
    if (start + s) % block_size != 0:
        raise ValueError(
            f"suffix length {s} must pad start {start} to a block multiple"
        )
    n_view = (start + s) // block_size
    cdt = compute_dtype(cfg)
    x = embed_apply(params["embed"], cfg, tokens)
    positions = start + jnp.arange(s)[None, :]
    _, per = _n_scan(cfg)

    def sub_suffix(p, x, kv):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _qkv(p["attn"], cfg, h)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        kc = jnp.swapaxes(k, 1, 2)                   # (1, Hkv, S, Dh)
        vc = jnp.swapaxes(v, 1, 2)
        k_pool = paged_write_rows(kv["k"], kc[0], table_row, block_size, start=start)
        v_pool = paged_write_rows(kv["v"], vc[0], table_row, block_size, start=start)
        view_tbl = table_row[None, :n_view]          # (1, n_view)
        k_view = paged_view(k_pool, view_tbl, block_size)  # (1, Hkv, start+S, Dh)
        v_view = paged_view(v_pool, view_tbl, block_size)
        # flash convention: queries are the LAST Sq positions of the key
        # sequence — with Skv = start + S that is exactly start..start+S-1
        attn = attend(
            jnp.swapaxes(q, 1, 2), k_view, v_view, causal=True
        )
        attn = jnp.swapaxes(attn, 1, 2).reshape(x.shape[0], s, -1)
        x = x + attn @ p["attn"]["wo"].astype(cdt)
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if "moe" in p:
            y, _ = moe_mod.moe_apply(p["moe"], cfg, h2)
        else:
            y = mlp_apply(p["mlp"], cfg, h2)
        return x + y, {"k": k_pool, "v": v_pool}

    def body(x, xs):
        blk, kvs = xs
        new_kvs = {}
        if per == 1:
            x, kv = sub_suffix(blk, x, kvs["pos0"])
            new_kvs["pos0"] = kv
        else:
            for i in range(per):
                sub = jax.tree_util.tree_map(lambda v: v[i], blk)
                x, kv = sub_suffix(sub, x, kvs[f"pos{i}"])
                new_kvs[f"pos{i}"] = kv
        return x, new_kvs

    x, new_cache = lax.scan(body, x, (params["blocks"], cache))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = last_token_logits(params["embed"], cfg, x, lengths=lengths)
    return logits, new_cache
