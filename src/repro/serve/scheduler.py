"""Token-level continuous batching over the paged KV cache.

The static :class:`~repro.serve.engine.Engine` serves a batch whole: pad
every prompt to a common length, prefill once, decode
until the LAST sequence finishes.  Real serving traffic is ragged — a
handful of long generations pin the batch while short ones sit finished
in their rows, and newly arrived requests wait for the whole batch to
drain.  This module decouples sequence lifetime from batch lifetime:

* **Paged KV cache.**  Each slot's cache rows live in fixed-size blocks
  of a preallocated pool (:mod:`repro.serve.kvcache`), addressed through
  a per-slot block table.  Admitting or evicting a sequence edits the
  table — never reshapes device state — so the jitted decode step traces
  exactly once for the lifetime of the engine.
* **Slot admission, EOS eviction.**  Between decode steps the leader
  admits queued prefills into free batch slots (reserve-at-admission:
  a request either gets every block it can touch or stays queued — pool
  exhaustion is pure backpressure) and evicts finished sequences, whose
  blocks return to the free list for the next admit.
* **Leader-combining decode loop** (ported from
  :class:`repro.service.scheduler.MicroBatcher`): there is no engine
  thread.  The submitting thread that finds no leader becomes the
  leader and runs admit→decode→evict for *everyone* until no work
  remains; arrivals during a step join at the next step boundary.  A
  lone caller therefore pays zero coordination latency, and leadership
  hands off through the lock-release/re-check dance rather than a
  parked-thread wakeup.
* **Prefix-cache sharing.**  Admission probes a
  :class:`~repro.serve.kvcache.PrefixIndex` keyed by rolling hashes of
  full token blocks: on a hit the slot *adopts* the resident blocks
  (refcount bump, zero prefill compute for those tokens) and prefills
  only the suffix through the chunked
  :func:`~repro.models.transformer.lm_prefill_suffix` path — logits are
  bit-identical to full prefill, so greedy outputs are byte-identical
  with sharing on or off.  Every admitted prompt publishes its full
  blocks back to the index; under pool pressure the index LRU-evicts
  entries whose blocks nothing else holds.  Sharing is bypassed where
  bitwise prefill reproducibility doesn't hold (MoE capacity routing is
  batch-shape-dependent) or positions are offset (VLM image tokens).

Emission is byte-compatible with the static engine's greedy path: the
first token is the argmax of the prefill logits at the true last prompt
position, decode feeds token *k* at position ``len + k - 1``, and a
sequence stops after emitting EOS or ``max_new_tokens`` tokens.  On a
uniform batch the two engines produce identical ``token_ids``
(``tests/test_continuous_batching.py`` pins this bitwise).

Sharded serving: pass ``mesh`` (and the ``param_specs`` returned by
``api.init``), as for the static engine.  The weights go to their logical
shardings, the pool is made already split by KV head (its
``paged_cache_init`` specs) and every program that returns it pins it to
that layout, so the donated pool stays in place from step to step; each
step's host inputs are put on the mesh replicated (span ``engine.place``,
``ContinuousStats.place_s``), and the leader runs admission, prefill, the
paged write and decode inside the mesh context.  Without a mesh every
program is the one traced before meshes existed.

Per-request SLO accounting records time-to-first-token (submit → prefill
argmax) and inter-token latency (consecutive decode materializations) in
bounded windows; ``slo_ms()`` reports p50/p99 of both.  The time each
admitted request waited in the queue adds to
``ContinuousStats.queue_wait_s``.  The loop's host work is in profiler
spans (:mod:`repro.trace`): ``engine.admit`` around each admission, with
``engine.prefill`` (dispatch through the first token on the host) inside
it, and per decode step ``engine.step`` (input upload, dispatch, the
host sync; on a mesh ``engine.place`` inside it, the upload) then
``engine.emit`` (emit and evict).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.tokenizer import ByteTokenizer
from repro.models.registry import build_model
from repro.launch.sharding import abstract, replicated, shardings_from_specs
from repro.serve.engine import (
    GenerationResult, ServeConfig, mesh_context, place_params,
)
from repro.serve.kvcache import (
    BlockManager, PagedCacheSpec, PrefixIndex, blocks_for,
)
from repro.trace import span

__all__ = ["ContinuousEngine", "ContinuousStats", "EngineClosed"]


class EngineClosed(RuntimeError):
    """The engine is closed; the request was or will never be admitted."""

# Bounded windows for TTFT / inter-token latency percentiles.
_SLO_WINDOW = 8192


@dataclasses.dataclass
class ContinuousStats:
    """Cumulative scheduler counters (allocator stats live on the manager)."""

    requests: int = 0
    completed: int = 0
    failed: int = 0             # futures resolved with an exception
    cancelled: int = 0          # queued requests cancelled at close()
    prefills: int = 0
    steps: int = 0              # batched decode steps executed
    tokens_out: int = 0         # tokens emitted across all requests
    decode_tokens: int = 0      # tokens emitted by decode steps (excl. first)
    admission_stalls: int = 0   # head-of-queue blocked on slots or blocks
    peak_active: int = 0
    prefix_hits: int = 0        # admissions that adopted indexed blocks
    prefix_misses: int = 0      # prefix-eligible admissions with no match
    prefill_tokens_saved: int = 0  # prompt tokens whose prefill was skipped
    queue_wait_s: float = 0.0   # sum over admissions of (admission - submit)
    place_s: float = 0.0        # putting step inputs on the mesh (0 without one)
    # per layer, summed over decode steps and active slots: the blocks
    # below each slot's length, which paged decode attention reads, and
    # the whole table width, which a gather of each slot's view reads
    kv_blocks_read: int = 0
    kv_blocks_viewed: int = 0

    @property
    def tokens_per_step(self) -> float:
        """Mean kept tokens per decode step (≤ max_slots; lane occupancy)."""
        return self.decode_tokens / self.steps if self.steps else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        probes = self.prefix_hits + self.prefix_misses
        return self.prefix_hits / probes if probes else 0.0


class _Seq:
    """Host-side state of one admitted sequence (leader-thread only)."""

    __slots__ = (
        "future", "prompt_len", "budget", "tokens", "t_submit",
        "prefill_s", "t_first", "t_last", "fed",
    )

    def __init__(self, future, prompt_len, budget, t_submit, prefill_s, now):
        self.future: "Future[GenerationResult]" = future
        self.prompt_len = prompt_len
        self.budget = budget
        self.tokens: List[int] = []
        self.t_submit = t_submit
        self.prefill_s = prefill_s
        self.t_first = now
        self.t_last = now
        self.fed = 0            # decode steps this sequence was fed into


class _Request:
    __slots__ = ("prompt", "budget", "future", "t_submit", "seed", "rid")

    def __init__(self, prompt: List[int], budget: int, seed: int = 0):
        self.prompt = prompt
        self.budget = budget
        self.seed = seed
        self.rid = 0            # submission ordinal, set under the lock
        self.future: "Future[GenerationResult]" = Future()
        self.t_submit = time.perf_counter()


class ContinuousEngine:
    """``submit(text) -> Future`` serving over a paged pool of decode slots.

    Greedy by default; with ``greedy=False`` each request samples
    (temperature + top-k) under its own PRNG key derived from a
    per-request seed folded with the token index — never a shared or
    lane-positional key — so sampled outputs are a pure function of
    (prompt, seed), independent of lane composition and eviction order.
    The byte-parity tests against the static engine keep running greedy.
    ``generate(texts)`` is a thin batch wrapper: enqueue all, lead once,
    gather in order.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        spec: PagedCacheSpec,
        scfg: ServeConfig = ServeConfig(),
        prefix_cache: bool = True,
        mesh=None,
        param_specs=None,
    ):
        self.cfg = cfg
        self.api = build_model(cfg)
        if not self.api.supports_paged:
            raise ValueError(
                f"model family {cfg.family!r} (windows="
                f"{getattr(cfg, 'window', None)}) has no paged-KV decode "
                "path; use the static Engine"
            )
        self.spec = spec
        self.scfg = scfg
        self.mesh = mesh
        self.params = place_params(params, mesh, param_specs)
        self.tok = ByteTokenizer()
        self.stats = ContinuousStats()
        self._offset = cfg.n_img_tokens or 0

        self._mgr = BlockManager(spec)
        if mesh is None:
            self._cache, _ = self.api.paged_cache_init(
                spec.n_blocks, spec.block_size)
            self._put = jnp.asarray
            pin = lambda c: c  # noqa: E731
        else:
            def pool():
                return self.api.paged_cache_init(spec.n_blocks, spec.block_size)

            shapes, specs = abstract(pool)
            cache_sh = shardings_from_specs(mesh, specs, shapes)
            # made in place, split: the whole pool never sits on one device
            self._cache = jax.jit(lambda: pool()[0], out_shardings=cache_sh)()
            rep_sh = replicated(mesh)
            self._put = lambda x: jax.device_put(x, rep_sh)  # noqa: E731

            def pin(c):
                return jax.lax.with_sharding_constraint(c, cache_sh)

        # Prefix sharing needs bitwise-reproducible prefill: MoE capacity
        # routing depends on the prefill batch shape (suffix vs full give
        # different drops), and VLM image tokens offset every position —
        # bypass both so sharing can never change bytes.
        self._prefix_enabled = bool(
            prefix_cache
            and self.api.prefill_suffix is not None
            and self._offset == 0
            and cfg.family != "moe"
        )
        self._index: Optional[PrefixIndex] = (
            PrefixIndex(self._mgr) if self._prefix_enabled else None
        )

        # Fixed-shape batched decode: admission/eviction only edit the
        # block tables and the (S,) token/pos vectors, so this traces once.
        bs = spec.block_size
        temp = float(max(scfg.temperature, 1e-6))
        top_k = int(getattr(scfg, "top_k", 0))

        def sample_rows(logits, seeds, idx):
            # one key per lane from (request seed, token index) ONLY —
            # re-running the same request in any lane mix reproduces it
            lg = logits.astype(jnp.float32) / temp
            if top_k > 0:
                kth = jax.lax.top_k(lg, min(top_k, lg.shape[-1]))[0][..., -1:]
                lg = jnp.where(lg < kth, -jnp.inf, lg)
            keys = jax.vmap(
                lambda s, i: jax.random.fold_in(jax.random.PRNGKey(s), i)
            )(seeds, idx)
            return jax.vmap(jax.random.categorical)(keys, lg).astype(jnp.int32)

        if scfg.greedy:
            def step(p, cur, pos, tables, cache):
                logits, cache = self.api.decode_step_paged(
                    p, cur, pos, tables, cache, bs
                )
                return jnp.argmax(logits, -1).astype(jnp.int32), pin(cache)
        else:
            def step(p, cur, pos, tables, cache, seeds, idx):
                logits, cache = self.api.decode_step_paged(
                    p, cur, pos, tables, cache, bs
                )
                return sample_rows(logits, seeds, idx), pin(cache)

        self._step = jax.jit(step, donate_argnums=(4,))
        self._sample_first = jax.jit(
            lambda lg, seed: sample_rows(
                lg[None], seed[None], jnp.zeros((1,), jnp.int32)
            )[0]
        )
        self._prefill = jax.jit(
            lambda p, b: self.api.prefill(p, b, max_len=spec.max_len)
        )
        self._write = jax.jit(
            lambda c, pc, row: pin(self.api.paged_prefill_write(c, pc, row, bs)),
            donate_argnums=(0,),
        )
        # suffix prefill retraces per (suffix bucket, start) pair — both
        # multiples of block_size and bounded by the table width M, so the
        # trace count is bounded by M² for the engine's lifetime
        def suffix(p, t, start, row, c, lengths):
            logits, c = self.api.prefill_suffix(
                p, t, start, row, c, bs, lengths=lengths
            )
            return logits, pin(c)

        self._prefill_suffix = jax.jit(
            lambda p, t, start, row, c, lengths: suffix(
                p, t, start, row, c, lengths),
            static_argnums=(2,),
            donate_argnums=(4,),
        )

        # Leader-only decode state (no lock: exactly one leader at a time).
        self._cur = np.zeros((spec.max_slots, 1), np.int32)
        self._pos = np.zeros((spec.max_slots,), np.int32)
        self._seeds = np.zeros((spec.max_slots,), np.uint32)
        self._idx = np.zeros((spec.max_slots,), np.int32)
        self._active: Dict[int, _Seq] = {}
        self._free_slots: List[int] = list(range(spec.max_slots - 1, -1, -1))
        self._tables_dev = self._put(self._mgr.tables)
        self._tables_dirty = False

        self._lock = threading.Lock()      # queue, stop flag, SLO windows
        self._leader = threading.Lock()    # at most one decode loop
        self._queue: Deque[_Request] = deque()
        self._stop = False
        self._ttft_ms: Deque[float] = deque(maxlen=_SLO_WINDOW)
        self._itl_ms: Deque[float] = deque(maxlen=_SLO_WINDOW)

    # -- client surface ------------------------------------------------------

    def submit(
        self,
        text: str,
        max_new_tokens: Optional[int] = None,
        lead: bool = True,
        seed: Optional[int] = None,
    ) -> "Future[GenerationResult]":
        """Enqueue one prompt; the future resolves to a GenerationResult.

        The calling thread may transparently become the leader and run
        the decode loop for every queued and active request until no
        work remains (``lead=False`` only enqueues — ``generate`` uses
        it to stage a batch before leading once).

        ``seed`` keys this request's sampling stream (``greedy=False``);
        when omitted it derives from ``scfg.seed`` and the submission
        ordinal — pass it explicitly when replaying a workload across
        threads, where submission order isn't deterministic.
        """
        budget = max_new_tokens or self.scfg.max_new_tokens
        req = _Request(self.tok.encode(text, add_eos=False), budget)
        total = self._offset + len(req.prompt) + budget - 1
        if budget < 1:
            req.future.set_exception(ValueError("max_new_tokens must be >= 1"))
            return req.future
        if total > self.spec.max_len:
            req.future.set_exception(
                ValueError(
                    f"prompt+budget needs {total} cache rows > max_len "
                    f"{self.spec.max_len} "
                    f"({self.spec.max_blocks_per_seq} blocks × "
                    f"{self.spec.block_size})"
                )
            )
            return req.future
        with self._lock:
            if self._stop:
                raise EngineClosed("engine is closed")
            self.stats.requests += 1
            req.rid = self.stats.requests
            req.seed = seed if seed is not None else (
                self.scfg.seed + self.stats.requests
            )
            self._queue.append(req)
        if lead:
            self._maybe_lead()
        return req.future

    def generate(
        self, texts: List[str], max_new_tokens: Optional[int] = None
    ) -> List[GenerationResult]:
        """Batch wrapper: enqueue everything, lead once, gather in order."""
        futs = [self.submit(t, max_new_tokens, lead=False) for t in texts]
        self._maybe_lead()
        return [f.result() for f in futs]

    # -- leader-combining decode loop ----------------------------------------

    def _maybe_lead(self) -> None:
        # Non-blocking: if a leader exists it will admit our request at
        # its next step boundary.  The re-check loop closes the race
        # where the old leader saw an empty queue and was releasing just
        # as we enqueued.
        while True:
            with self._lock:
                work = bool(self._queue) and not self._stop
            if not work or not self._leader.acquire(blocking=False):
                return
            try:
                self._run_loop()
            finally:
                self._leader.release()

    def _run_loop(self) -> None:
        """Admit → decode one token for every active slot → evict; repeat.

        Runs on the submitting thread that won leadership.  An exception
        (OOM, poisoned weights) is delivered to every *active* future —
        a dying leader must not strand callers — then swallowed so it
        can't tear down an unrelated client thread; queued requests stay
        queued for the next leader.
        """
        try:
            with mesh_context(self.mesh):
                while True:
                    self._admit()
                    if not self._active:
                        with self._lock:
                            if not self._queue or self._stop:
                                return
                        continue  # backpressure cleared by an eviction race
                    self._decode_once()
        except BaseException as e:  # noqa: BLE001 — delivered first
            for slot, seq in list(self._active.items()):
                if not seq.future.done():
                    seq.future.set_exception(e)
                self.stats.failed += 1
                self._mgr.release(slot)
                self._free_slots.append(slot)
            self._active.clear()
            self._tables_dirty = True
            if isinstance(e, (SystemExit, KeyboardInterrupt)):
                raise

    def _probe(self, prompt: List[int]):
        """Longest indexed block-aligned prefix → (blocks, n_tokens)."""
        if self._index is None:
            return [], 0
        return self._index.match(prompt)

    def _admit(self) -> None:
        """Move queued requests into free slots, strictly FIFO.

        Head-of-line blocking is deliberate: skipping a big request to
        admit later small ones would starve it under sustained load, and
        FIFO keeps the backpressure tests deterministic.  Under pool
        pressure the prefix index gives blocks back (LRU entries whose
        blocks nothing else holds) before the head request stalls or
        fails — index residency is a cache, never a reservation.
        """
        while self._free_slots:
            with self._lock:
                if self._stop or not self._queue:
                    return
                req = self._queue[0]
            total = self._offset + len(req.prompt) + req.budget - 1
            # leader-only state below (index, allocator): the lock above
            # only guards the queue — nobody else pops it
            adopt, start = self._probe(req.prompt)
            if not self._mgr.can_admit(total, n_adopted=len(adopt)):
                if self._index is not None:
                    shortfall = (
                        blocks_for(total, self.spec.block_size)
                        - len(adopt) - self._mgr.n_free
                    )
                    if shortfall > 0 and self._index.evict_for(shortfall):
                        # eviction may have dropped the matched entry (or
                        # unlocked a shorter one): probe again
                        adopt, start = self._probe(req.prompt)
                if not self._mgr.can_admit(total, n_adopted=len(adopt)):
                    if self._active:
                        # an eviction will free blocks: wait at the head
                        self.stats.admission_stalls += 1
                        return
                    # leader is the sole allocator and the index has been
                    # drained of reclaimable blocks, so an idle pool is a
                    # FULL pool — this request can never fit; stalling
                    # here would spin the loop forever
                    with self._lock:
                        if self._stop:
                            return  # close() already failed the queue
                        self._queue.popleft()
                        self.stats.failed += 1
                    req.future.set_exception(
                        RuntimeError(
                            f"request needs {blocks_for(total, self.spec.block_size)} "
                            f"blocks but the pool only has "
                            f"{self.spec.usable_blocks} usable"
                        )
                    )
                    continue
            with self._lock:
                if self._stop:
                    return
                self._queue.popleft()
            if not req.future.set_running_or_notify_cancel():
                with self._lock:
                    self.stats.cancelled += 1
                continue
            with span("engine.admit", request=req.rid):
                self._admit_one(req, total, adopt, start)
        # no free slot for the head request: wait for an eviction

    def _admit_one(
        self, req: _Request, total: int, adopt: List[int], start: int
    ) -> None:
        prompt, budget = req.prompt, req.budget
        L = len(prompt)
        # Pad prompts up to a block-size multiple so distinct lengths
        # share prefill traces; the dense cache is always max_len rows
        # (what the paged write scatters), so this is the only retrace
        # axis.  Pad rows beyond ``lengths`` are overwritten by decode
        # before any read can see them — same invariant the static
        # engine's ragged batches rely on.
        bucket = min(
            self.spec.max_len - self._offset,
            blocks_for(L, self.spec.block_size) * self.spec.block_size,
        )
        t0 = time.perf_counter()
        self.stats.queue_wait_s += t0 - req.t_submit
        slot: Optional[int] = None
        with span("engine.prefill", request=req.rid, prompt_len=L,
                  bucket=bucket):
            if start > 0:
                # Prefix hit: the slot and its blocks come first (suffix
                # prefill writes through the block table), then only the
                # unmatched tail runs the model — ``start`` prompt tokens
                # cost zero prefill FLOPs.
                slot = self._free_slots.pop()
                admitted = self._mgr.admit(slot, total, prefix_blocks=adopt)
                assert admitted, "can_admit passed but admit failed (leader is sole allocator)"
                suf = np.full((1, bucket - start), self.tok.pad_id, np.int32)
                suf[0, : L - start] = prompt[start:]
                row = self._put(self._mgr.tables[slot])
                logits, self._cache = self._prefill_suffix(
                    self.params, self._put(suf), start, row, self._cache,
                    self._put(np.asarray([L - start], np.int32)),
                )
                dense = None
                self.stats.prefix_hits += 1
                self.stats.prefill_tokens_saved += start
            else:
                if self._prefix_enabled:
                    self.stats.prefix_misses += 1
                toks = np.full((1, bucket), self.tok.pad_id, np.int32)
                toks[0, :L] = prompt
                batch: Dict[str, Any] = {
                    "tokens": self._put(toks),
                    "lengths": self._put(np.asarray([L], np.int32)),
                }
                if self.cfg.family == "vlm":
                    batch["patch_embeds"] = self._put(np.zeros(
                        (1, self.cfg.n_img_tokens, self.cfg.d_model), np.float32
                    ))
                logits, dense = self._prefill(self.params, batch)
            first = self._first_token(logits, req.seed)
            now = time.perf_counter()
        prefill_s = now - t0
        self.stats.prefills += 1
        with self._lock:
            self._ttft_ms.append((now - req.t_submit) * 1e3)
        self.stats.tokens_out += 1

        if first == self.tok.eos_id or budget == 1:
            # Entirely served by prefill: occupies no slot past this
            # point.  A prefix hit already owns blocks — publish the
            # prompt's full blocks (the suffix KV is resident and exact)
            # before dropping the slot's hold, then let go.
            if slot is not None:
                if self._index is not None:
                    self._index.publish(
                        prompt, self._mgr.slot_blocks(slot), L
                    )
                self._mgr.release(slot)
                self._free_slots.append(slot)
                self._tables_dirty = True
            self.stats.completed += 1
            req.future.set_result(
                self._result([first], L, 0, prefill_s, 0.0)
            )
            return

        if slot is None:
            slot = self._free_slots.pop()
            admitted = self._mgr.admit(slot, total)
            assert admitted, "can_admit passed but admit failed (leader is sole allocator)"
            row = self._put(self._mgr.tables[slot])
            self._cache = self._write(self._cache, dense, row)
        if self._index is not None:
            # publish every full-block prefix: decode writes land in the
            # partial/fresh tail blocks, never in published ones
            self._index.publish(prompt, self._mgr.slot_blocks(slot), L)
        seq = _Seq(req.future, L, budget, req.t_submit, prefill_s, now)
        seq.tokens.append(first)
        self._cur[slot, 0] = first
        self._pos[slot] = self._offset + L
        self._seeds[slot] = req.seed & 0xFFFFFFFF
        self._idx[slot] = 1
        self._active[slot] = seq
        self._tables_dirty = True
        self.stats.peak_active = max(self.stats.peak_active, len(self._active))

    def _first_token(self, logits, seed: int) -> int:
        """First emitted token from the prefill logits (greedy or sampled
        with this request's key at token index 0)."""
        if self.scfg.greedy:
            return int(jnp.argmax(logits[0]))
        return int(
            self._sample_first(
                logits[0], self._put(np.uint32(seed & 0xFFFFFFFF))
            )
        )

    def _decode_once(self) -> None:
        """One batched paged decode step + host-side emit/evict."""
        step = self.stats.steps + 1
        live = self._pos[list(self._active)] + 1
        self.stats.kv_blocks_read += int(
            (-(-live // self.spec.block_size)).sum())
        self.stats.kv_blocks_viewed += len(live) * self.spec.max_blocks_per_seq
        with span("engine.step", step=step, active=len(self._active)):
            if self.mesh is None:
                inputs = self._step_inputs()
            else:
                t0 = time.perf_counter()
                with span("engine.place", step=step):
                    inputs = self._step_inputs()
                self.stats.place_s += time.perf_counter() - t0
            nxt, self._cache = self._step(*inputs)
            nxt = np.asarray(nxt)  # the one host sync per step: (S,) int32
            now = time.perf_counter()
        self.stats.steps = step
        with span("engine.emit", step=step):
            for slot, seq in list(self._active.items()):
                tok = int(nxt[slot])
                seq.fed += 1
                seq.tokens.append(tok)
                with self._lock:
                    self._itl_ms.append((now - seq.t_last) * 1e3)
                seq.t_last = now
                self.stats.tokens_out += 1
                self.stats.decode_tokens += 1
                if tok == self.tok.eos_id or len(seq.tokens) >= seq.budget:
                    self._evict(slot, seq, now)
                else:
                    self._cur[slot, 0] = tok
                    self._pos[slot] += 1
                    self._idx[slot] = len(seq.tokens)

    def _step_inputs(self) -> tuple:
        """The decode step's arguments, host state uploaded (the block
        tables only after a change)."""
        if self._tables_dirty:
            self._tables_dev = self._put(self._mgr.tables)
            self._tables_dirty = False
        args = (self.params, self._put(self._cur), self._put(self._pos),
                self._tables_dev, self._cache)
        if not self.scfg.greedy:
            args = args + (self._put(self._seeds), self._put(self._idx))
        return args

    def _evict(self, slot: int, seq: _Seq, now: float) -> None:
        self._mgr.release(slot)
        self._tables_dirty = True
        del self._active[slot]
        self._free_slots.append(slot)
        self._cur[slot, 0] = 0
        self._pos[slot] = 0
        self.stats.completed += 1
        seq.future.set_result(
            self._result(
                seq.tokens, seq.prompt_len, seq.fed, seq.prefill_s,
                now - seq.t_first,
            )
        )

    def _result(self, tokens, prompt_len, steps, prefill_s, decode_s):
        return GenerationResult(
            text=self.tok.decode(tokens),
            token_ids=list(tokens),
            prompt_len=prompt_len,
            steps=steps,
            prefill_s=prefill_s,
            decode_s=decode_s,
        )

    # -- accounting ----------------------------------------------------------

    def slo_ms(self) -> Dict[str, float]:
        """TTFT and inter-token latency percentiles (bounded windows)."""
        with self._lock:
            ttft = list(self._ttft_ms)
            itl = list(self._itl_ms)

        def pct(xs: List[float], p: float) -> float:
            return float(np.percentile(xs, p)) if xs else 0.0

        return {
            "ttft_p50_ms": pct(ttft, 50),
            "ttft_p99_ms": pct(ttft, 99),
            "itl_p50_ms": pct(itl, 50),
            "itl_p99_ms": pct(itl, 99),
            "ttft_mean_ms": float(np.mean(ttft)) if ttft else 0.0,
            "itl_mean_ms": float(np.mean(itl)) if itl else 0.0,
        }

    def reset_slo(self) -> None:
        """Drop the SLO windows (benchmarks: exclude warmup/compile TTFT)."""
        with self._lock:
            self._ttft_ms.clear()
            self._itl_ms.clear()

    def counters(self) -> Dict[str, float]:
        """Flat cumulative counters (loadgen ``counters_fn`` shape)."""
        out = {k: float(v) for k, v in dataclasses.asdict(self.stats).items()}
        out["tokens_per_step"] = self.stats.tokens_per_step
        out["prefix_hit_rate"] = self.stats.prefix_hit_rate
        out.update({f"blk_{k}": float(v) for k, v in self._mgr.stats().items()})
        if self._index is not None:
            out.update(
                {f"pfx_{k}": float(v) for k, v in self._index.stats().items()}
            )
        return out

    def check(self) -> None:
        """Assert allocator + prefix-index consistency (tests + debug):
        every block's refcount must equal its slot holds plus its index
        holds, exactly."""
        self._mgr.check(
            self._index.block_refs() if self._index is not None else None
        )

    # -- shutdown ------------------------------------------------------------

    def close(self, drain: bool = False) -> None:
        """Stop admitting; fail queued requests; wait out the leader.

        Queued-but-unadmitted futures resolve with :class:`EngineClosed`
        — a caller blocked on ``.result()`` gets a clear error instead
        of waiting forever.  Active sequences always finish their decode
        (bounded by the largest remaining budget): the leader keeps
        decoding but admits nothing once the stop flag is up.

        ``drain=True`` first serves everything already queued (leading
        if necessary), so no request submitted before ``close`` is lost.
        """
        if drain:
            while True:
                with self._lock:
                    if self._stop or not self._queue:
                        break
                self._maybe_lead()
                with self._leader:
                    pass  # an existing leader is draining; wait it out
        with self._lock:
            if self._stop:
                return
            self._stop = True
            for req in self._queue:
                if req.future.set_running_or_notify_cancel():
                    req.future.set_exception(
                        EngineClosed(
                            "engine is closed; request was queued but "
                            "never admitted"
                        )
                    )
                self.stats.cancelled += 1
            self._queue.clear()
        with self._leader:
            pass  # leader drains its active set, then we own shutdown

    def __enter__(self) -> "ContinuousEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
