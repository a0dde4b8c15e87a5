"""Batched serving engine: prefill + decode over the uniform model API.

Static-batch engine: a batch of requests is padded to a common prefill
length, prefilled once, then decoded token-by-token with per-sequence
positions until EOS or the token budget.  Per-sequence positions (not a
scalar clock) are what real continuous-batching serving needs — finished
sequences keep their cache rows and are masked out of sampling.

Sharded serving: pass ``mesh`` (and the ``param_specs`` returned by
``api.init``) and the engine device_puts the weights to their logical
shardings, shards the batch over the data-parallel axes, and runs prefill
and every decode step inside the mesh context so the models' ``constrain``
annotations (:mod:`repro.dist.logical`) take effect — batched decode then
shards across devices.  Without a mesh nothing changes: single-device
serving traces the identical jaxpr.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.tokenizer import ByteTokenizer
from repro.launch.sharding import batch_shardings, replicated, shardings_from_specs
from repro.models.registry import ModelApi, build_model

__all__ = ["ServeConfig", "Engine", "GenerationResult", "mesh_context",
           "place_params"]


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    max_len: int = 512
    greedy: bool = True
    temperature: float = 1.0
    top_k: int = 0            # 0 = no top-k truncation (sampling engines)
    seed: int = 0
    # Host-sync cadence of the decode loop: emitted tokens accumulate in a
    # device-side buffer and the all-done flag is polled only every
    # ``sync_every`` steps (1 = poll every step, the old behavior; the
    # token buffer itself transfers ONCE per generate call either way).
    sync_every: int = 8


@dataclasses.dataclass
class GenerationResult:
    text: str
    token_ids: List[int]
    prompt_len: int
    steps: int
    prefill_s: float
    decode_s: float

    @property
    def tokens_per_s(self) -> float:
        return self.steps / self.decode_s if self.decode_s > 0 else float("inf")


def mesh_context(mesh):
    """``jax.set_mesh(mesh)`` (activates the models' sharding rules), or a
    no-op without a mesh."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.set_mesh(mesh)


def place_params(params, mesh, param_specs=None):
    """The weights at their logical shardings on ``mesh`` (replicated
    without ``param_specs``); without a mesh they stay where they are."""
    if mesh is None:
        return params
    sh = (shardings_from_specs(mesh, param_specs, params)
          if param_specs is not None else replicated(mesh))
    return jax.device_put(params, sh)


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        scfg: ServeConfig = ServeConfig(),
        mesh=None,
        param_specs=None,
    ):
        self.cfg = cfg
        self.api = build_model(cfg)
        self.scfg = scfg
        self.mesh = mesh
        self.tok = ByteTokenizer()
        self.params = place_params(params, mesh, param_specs)
        self._prefill = jax.jit(
            lambda p, batch: self.api.prefill(p, batch, max_len=scfg.max_len)
        )
        self._decode = jax.jit(self.api.decode_step, donate_argnums=(3,))
        # Fused emit+decode step: token emission, EOS bookkeeping and the
        # decode itself run in ONE jitted call that carries a device-side
        # output buffer — no per-token host transfers (§Perf: the old loop
        # pulled every token with int(cur[i, 0]), B transfers per step).
        eos = self.tok.eos_id
        pad = self.tok.pad_id

        def fused(p, cur, pos, cache, out_buf, n_emit, done, t, key):
            val = jnp.where(done[:, None], pad, cur)
            out_buf = jax.lax.dynamic_update_slice(out_buf, val, (0, t))
            n_emit = n_emit + (~done).astype(jnp.int32)
            done = done | (cur[:, 0] == eos)
            logits, cache = self.api.decode_step(p, cur, pos, cache)
            if self.scfg.greedy:
                nxt = jnp.argmax(logits, -1)
            else:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(
                    sub, logits / self.scfg.temperature, axis=-1
                )
            cur = nxt[:, None].astype(jnp.int32)
            return cur, pos + 1, cache, out_buf, n_emit, done, key

        self._fused_step = jax.jit(fused, donate_argnums=(3, 4, 5, 6))

    def _shard_batch(self, extras: Dict[str, Any]) -> Dict[str, Any]:
        """Spread the request batch over the mesh's data-parallel axes."""
        if self.mesh is None:
            return extras
        sh = batch_shardings(
            self.mesh,
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in extras.items()},
        )
        return {k: jax.device_put(v, sh[k]) for k, v in extras.items()}

    def _pad_prompts(self, prompts: List[List[int]]) -> Tuple[np.ndarray, np.ndarray]:
        """Left-align prompts, pad right to the longest (positions differ)."""
        maxlen = max(len(p) for p in prompts)
        toks = np.full((len(prompts), maxlen), self.tok.pad_id, np.int32)
        lens = np.zeros((len(prompts),), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : len(p)] = p
            lens[i] = len(p)
        return toks, lens

    def generate(self, texts: List[str]) -> List[GenerationResult]:
        prompts = [self.tok.encode(t, add_eos=False) for t in texts]
        toks, lens = self._pad_prompts(prompts)
        b, s = toks.shape
        extras: Dict[str, Any] = {
            "tokens": jnp.asarray(toks),
            # true prompt lengths: prefill gathers each sequence's OWN
            # last-position logits, so ragged right-padded batches start
            # greedy continuation correctly (not from a pad row)
            "lengths": jnp.asarray(lens, jnp.int32),
        }
        if self.cfg.family == "encdec":
            extras["frames"] = jnp.zeros(
                (b, self.cfg.enc_frames, self.cfg.d_model), jnp.float32
            )
        if self.cfg.family == "vlm":
            extras["patch_embeds"] = jnp.zeros(
                (b, self.cfg.n_img_tokens, self.cfg.d_model), jnp.float32
            )

        extras = self._shard_batch(extras)
        t0 = time.perf_counter()
        with mesh_context(self.mesh):
            logits, cache = self._prefill(self.params, extras)
        logits.block_until_ready()
        prefill_s = time.perf_counter() - t0

        offset = self.cfg.n_img_tokens or 0
        pos = jnp.asarray(lens + offset, jnp.int32)
        cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out_buf = jnp.full((b, self.scfg.max_new_tokens), self.tok.pad_id,
                           jnp.int32)
        n_emit = jnp.zeros((b,), jnp.int32)
        done = jnp.zeros((b,), bool)
        key = jax.random.PRNGKey(self.scfg.seed)

        # Decode loop: tokens accumulate device-side; the host polls only
        # the all-done flag every ``sync_every`` steps and materializes the
        # token buffer once after the loop.
        t1 = time.perf_counter()
        steps = 0
        sync_every = max(1, self.scfg.sync_every)
        for step in range(self.scfg.max_new_tokens):
            if step % sync_every == 0 and step and bool(jnp.all(done)):
                break
            with mesh_context(self.mesh):
                cur, pos, cache, out_buf, n_emit, done, key = (
                    self._fused_step(
                        self.params, cur, pos, cache, out_buf, n_emit,
                        done, np.int32(step), key,
                    )
                )
            steps += 1
        out_buf.block_until_ready()
        decode_s = time.perf_counter() - t1

        out_np = np.asarray(out_buf)            # ONE transfer per flush
        emitted = np.asarray(n_emit)
        outs = [out_np[i, : emitted[i]].tolist() for i in range(b)]
        return [
            GenerationResult(
                text=self.tok.decode(outs[i]),
                token_ids=outs[i],
                prompt_len=int(lens[i]),
                steps=steps,
                prefill_s=prefill_s,
                decode_s=decode_s,
            )
            for i in range(b)
        ]
