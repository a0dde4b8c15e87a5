"""Uniform model API over all assigned architecture families.

``build_model(cfg)`` returns a :class:`ModelApi` with the same five entry
points regardless of family — the trainer, the serving engines and the
chip benchmark program against this interface only:

  init(key)                       → (params, param_specs)
  loss(params, batch)             → (scalar, metrics)       [train_step core]
  prefill(params, batch, max_len) → (last_logits, cache)
  decode_step(params, token, pos, cache) → (logits, cache)  [serve_step core]
  cache_init(batch, max_len)      → (cache, cache_specs)

``batch["lengths"]`` (B,) in prefill gathers each sequence's true
last-prompt-position logits, so ragged right-padded batches don't start
greedy continuation from a pad row.

Families that support the paged (block) KV cache — the continuous-batching
serving path — additionally expose three optional entry points (``None``
elsewhere; the continuous engine refuses politely):

  paged_cache_init(n_blocks, block_size)           → (cache, cache_specs)
  decode_step_paged(params, token, pos, tables, cache, block_size)
                                                   → (logits, cache)
  paged_prefill_write(cache, prefill_cache, table_row, block_size, start=0)
                                                   → cache
  prefill_suffix(params, tokens, start, table_row, cache, block_size,
                 lengths)                          → (logits, cache)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

from repro.configs.base import ModelConfig

from . import encdec, hybrid, ssm, transformer

__all__ = ["ModelApi", "build_model"]

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ModelApi:
    cfg: ModelConfig
    init: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable
    cache_init: Callable
    # paged-KV serving contract (continuous batching); None where unsupported
    paged_cache_init: Optional[Callable] = None
    decode_step_paged: Optional[Callable] = None
    paged_prefill_write: Optional[Callable] = None
    prefill_suffix: Optional[Callable] = None

    @property
    def supports_paged(self) -> bool:
        return self.decode_step_paged is not None


def _transformer_api(cfg: ModelConfig) -> ModelApi:
    def loss(params, batch):
        return transformer.lm_loss(
            params,
            cfg,
            batch["tokens"],
            loss_mask=batch.get("loss_mask"),
            extra_embeds=batch.get("patch_embeds"),
        )

    def prefill(params, batch, max_len=None):
        return transformer.lm_prefill(
            params,
            cfg,
            batch["tokens"],
            extra_embeds=batch.get("patch_embeds"),
            max_len=max_len,
            lengths=batch.get("lengths"),
        )

    # paged serving covers global-attention stacks only (no sliding-window
    # ring buffers in the block pool yet) — gate here so Engine/scheduler
    # can introspect support instead of tracing into a NotImplementedError
    paged = not any(w is not None for w in transformer.layer_windows(cfg))
    return ModelApi(
        cfg=cfg,
        init=lambda key: transformer.init_lm(cfg, key),
        loss=loss,
        prefill=prefill,
        decode_step=lambda p, t, pos, c: transformer.lm_decode_step(p, cfg, t, pos, c),
        cache_init=lambda b, m: transformer.lm_cache_init(cfg, b, m),
        paged_cache_init=(
            (lambda n, bs: transformer.lm_paged_cache_init(cfg, n, bs))
            if paged else None
        ),
        decode_step_paged=(
            (lambda p, t, pos, tb, c, bs:
             transformer.lm_decode_step_paged(p, cfg, t, pos, tb, c, bs))
            if paged else None
        ),
        paged_prefill_write=(
            (lambda c, pc, row, bs, start=0:
             transformer.lm_paged_prefill_write(cfg, c, pc, row, bs, start=start))
            if paged else None
        ),
        prefill_suffix=(
            (lambda p, t, start, row, c, bs, lengths=None:
             transformer.lm_prefill_suffix(
                 p, cfg, t, start, row, c, bs, lengths=lengths))
            if paged else None
        ),
    )


def _hybrid_api(cfg: ModelConfig) -> ModelApi:
    return ModelApi(
        cfg=cfg,
        init=lambda key: hybrid.init_hybrid(cfg, key),
        loss=lambda p, batch: hybrid.hybrid_loss(
            p, cfg, batch["tokens"], loss_mask=batch.get("loss_mask")
        ),
        prefill=lambda p, batch, max_len=None: hybrid.hybrid_prefill(
            p, cfg, batch["tokens"], max_len=max_len,
            lengths=batch.get("lengths"),
        ),
        decode_step=lambda p, t, pos, c: hybrid.hybrid_decode_step(p, cfg, t, pos, c),
        cache_init=lambda b, m: hybrid.hybrid_cache_init(cfg, b, m),
    )


def _ssm_api(cfg: ModelConfig) -> ModelApi:
    return ModelApi(
        cfg=cfg,
        init=lambda key: ssm.init_ssm(cfg, key),
        loss=lambda p, batch: ssm.ssm_loss(
            p, cfg, batch["tokens"], loss_mask=batch.get("loss_mask")
        ),
        prefill=lambda p, batch, max_len=None: ssm.ssm_prefill(
            p, cfg, batch["tokens"], max_len=max_len,
            lengths=batch.get("lengths"),
        ),
        decode_step=lambda p, t, pos, c: ssm.ssm_decode_step(p, cfg, t, pos, c),
        cache_init=lambda b, m: ssm.ssm_cache_init(cfg, b, m),
    )


def _encdec_api(cfg: ModelConfig) -> ModelApi:
    return ModelApi(
        cfg=cfg,
        init=lambda key: encdec.init_encdec(cfg, key),
        loss=lambda p, batch: encdec.encdec_loss(
            p, cfg, batch["frames"], batch["tokens"],
            loss_mask=batch.get("loss_mask"),
        ),
        prefill=lambda p, batch, max_len=None: encdec.encdec_prefill(
            p, cfg, batch["frames"], batch["tokens"], max_len=max_len,
            lengths=batch.get("lengths"),
        ),
        decode_step=lambda p, t, pos, c: encdec.encdec_decode_step(p, cfg, t, pos, c),
        cache_init=lambda b, m: encdec.encdec_cache_init(cfg, b, m),
    )


def build_model(cfg: ModelConfig) -> ModelApi:
    if cfg.family in ("dense", "moe", "vlm"):
        return _transformer_api(cfg)
    if cfg.family == "hybrid":
        return _hybrid_api(cfg)
    if cfg.family == "ssm":
        return _ssm_api(cfg)
    if cfg.family == "encdec":
        return _encdec_api(cfg)
    raise ValueError(f"unknown family {cfg.family!r}")
