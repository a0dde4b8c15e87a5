"""Paged KV cache + continuous-batching scheduler tests.

The load-bearing claims, each pinned bitwise where possible:

* block allocator invariants (no double-free, deterministic reuse,
  exhaustion is backpressure — not corruption);
* the paged decode path is byte-identical to the dense-cache path;
* a block table rebuilt from freed-and-reused blocks decodes byte-
  identically to a fresh pool (eviction can't leak state);
* the continuous engine matches the static engine on uniform batches
  and per-prompt serial generation on ragged mixes;
* the static engine's ragged batches match per-prompt serial generation
  (the pad-logits regression: prefill must gather each sequence's true
  last-position logits, not the pad row's).
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.registry import build_model
from repro.serve.engine import Engine, ServeConfig
from repro.serve.kvcache import (
    TRASH_BLOCK, BlockManager, PagedCacheSpec, blocks_for,
)
from repro.serve.scheduler import ContinuousEngine


def _tiny_cfg(**kw):
    base = dataclasses.replace(
        get_config("yi-6b"),
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=300,
    )
    return dataclasses.replace(base, **kw)


MAX_LEN, BS = 64, 8


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny_cfg()
    params, _ = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def ref_engine(tiny):
    cfg, params = tiny
    # max_len must equal the paged view (blocks x block_size) for byte
    # parity; 20 tokens covers every per-request budget the tests use
    return Engine(cfg, params, ServeConfig(max_new_tokens=20, max_len=MAX_LEN))


def _spec(**kw):
    base = dict(n_blocks=33, block_size=BS, max_slots=3,
                max_blocks_per_seq=MAX_LEN // BS)
    base.update(kw)
    return PagedCacheSpec(**base)


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

def test_blocks_for():
    assert blocks_for(0, 8) == 0
    assert blocks_for(1, 8) == 1
    assert blocks_for(8, 8) == 1
    assert blocks_for(9, 8) == 2


def test_alloc_free_roundtrip_and_double_free():
    mgr = BlockManager(_spec(n_blocks=6, max_slots=2, max_blocks_per_seq=4))
    blocks = mgr.alloc(3)
    assert blocks is not None and len(set(blocks)) == 3
    assert TRASH_BLOCK not in blocks
    mgr.check()
    mgr.free(blocks)
    mgr.check()
    with pytest.raises(ValueError, match="double free"):
        mgr.free(blocks)
    with pytest.raises(ValueError, match="trash"):
        mgr.free([TRASH_BLOCK])


def test_alloc_exhaustion_counts_failures():
    mgr = BlockManager(_spec(n_blocks=4, max_slots=2, max_blocks_per_seq=4))
    assert mgr.alloc(4) is None          # only 3 usable (trash reserved)
    assert mgr.alloc_failures == 1
    got = mgr.alloc(3)
    assert got is not None and mgr.n_free == 0
    assert mgr.alloc(1) is None
    mgr.check()


def test_deterministic_reuse_after_free():
    # LIFO free list: freeing and re-allocating yields the same blocks in
    # the same order — the byte-parity-after-eviction tests rely on this
    mgr = BlockManager(_spec())
    a = mgr.alloc(4)
    mgr.free(a)
    b = mgr.alloc(4)
    assert b == list(reversed(a))
    mgr.free(b)
    assert mgr.alloc(4) == list(reversed(b))


def test_admit_release_tables():
    spec = _spec(n_blocks=9, max_slots=2, max_blocks_per_seq=4)
    mgr = BlockManager(spec)
    assert mgr.admit(0, 17)              # 3 blocks of 8
    assert mgr.admit(1, 25)              # 4 blocks
    assert not mgr.can_admit(9)          # 1 free < 2 needed
    with pytest.raises(ValueError, match="already admitted"):
        mgr.admit(0, 8)
    row = mgr.tables[0]
    assert (row[:3] != TRASH_BLOCK).all() and row[3] == TRASH_BLOCK
    mgr.check()
    mgr.release(0)
    assert (mgr.tables[0] == TRASH_BLOCK).all()
    with pytest.raises(ValueError, match="not admitted"):
        mgr.release(0)
    with pytest.raises(ValueError, match="table width"):
        mgr.admit(0, spec.max_len + 1)
    mgr.check()


def test_admit_whole_or_nothing():
    mgr = BlockManager(_spec(n_blocks=4, max_slots=2, max_blocks_per_seq=4))
    assert not mgr.admit(0, 32)          # needs 4, pool has 3
    assert mgr.n_free == 3 and mgr.n_in_use == 0   # state untouched
    assert mgr.alloc_failures == 1
    assert mgr.admit(0, 24)
    mgr.check()


# ---------------------------------------------------------------------------
# paged decode path (model level)
# ---------------------------------------------------------------------------

def test_paged_decode_bitwise_vs_dense(tiny):
    cfg, params = tiny
    api = build_model(cfg)
    assert api.supports_paged
    spec = _spec()
    mgr = BlockManager(spec)
    toks = jnp.asarray([[256] + list(b"InChI=1S/C4")], jnp.int32)
    L = toks.shape[1]
    batch = {"tokens": toks, "lengths": jnp.asarray([L], jnp.int32)}

    logits, dense = api.prefill(params, batch, max_len=MAX_LEN)
    cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    pos = jnp.asarray([L], jnp.int32)
    cache = dense
    ref = []
    for _ in range(5):
        lg, cache = api.decode_step(params, cur, pos, cache)
        ref.append(np.asarray(lg))
        cur = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        pos = pos + 1

    # same sequence through the paged path, in slot 1 of a 3-slot batch
    # (slots 0/2 inactive: all-trash tables, pos 0 — their lanes must not
    # perturb slot 1's bytes)
    paged, _ = api.paged_cache_init(spec.n_blocks, BS)
    assert mgr.admit(1, L + 6)
    logits2, dense2 = api.prefill(params, batch, max_len=MAX_LEN)
    paged = api.paged_prefill_write(
        paged, dense2, jnp.asarray(mgr.tables[1]), BS
    )
    cur = jnp.zeros((3, 1), jnp.int32)
    cur = cur.at[1, 0].set(jnp.argmax(logits2[0]).astype(jnp.int32))
    pos = jnp.asarray([0, L, 0], jnp.int32)
    tables = jnp.asarray(mgr.tables)
    for step in range(5):
        lg, paged = api.decode_step_paged(params, cur, pos, tables, paged, BS)
        assert np.array_equal(np.asarray(lg[1:2]), ref[step])
        cur = cur.at[1, 0].set(jnp.argmax(lg[1]).astype(jnp.int32))
        pos = pos.at[1].add(1)


def test_reused_blocks_decode_identically_to_fresh(tiny):
    # evict a sequence, admit another into the recycled blocks, and the
    # recycled pool must produce the same bytes as a brand-new engine
    cfg, params = tiny
    spec = _spec()
    scfg = ServeConfig(max_new_tokens=10, max_len=MAX_LEN)
    churned = ContinuousEngine(cfg, params, spec, scfg)
    churned.generate(["InChI=1S/C4H10", "xylene", "C6H6"])  # churn + evict
    assert churned._mgr.stats()["frees"] > 0
    fresh = ContinuousEngine(cfg, params, spec, scfg)
    probe = ["InChI=1S/C8H9NO2/", "ab"]
    got = [r.token_ids for r in churned.generate(probe)]
    want = [r.token_ids for r in fresh.generate(probe)]
    assert got == want
    churned._mgr.check()
    churned.close()
    fresh.close()


# the chat mix in small: ragged prompts and budgets through 3 slots, so
# slots are admitted and evicted while others decode (≥ 64 steps)
CHURN = [("InChI=1S/C4H10/c1-3", 30), ("ab", 12), ("C1=CC=CC=C1O", 25),
         ("xylene", 18), ("InChI=1S/C8H9NO2/c1-6", 30), ("C6H6", 9),
         ("smiles:CC(=O)O", 22), ("InChI=1S/H2O/h1H2", 28), ("q", 16),
         ("ethanol", 24)]


def _serve_churn(cfg, params):
    eng = ContinuousEngine(cfg, params, _spec(),
                           ServeConfig(max_new_tokens=30, max_len=MAX_LEN))
    futs = [eng.submit(t, b, lead=False) for t, b in CHURN]
    eng._maybe_lead()
    res = [f.result(timeout=600) for f in futs]
    stats = eng.stats
    eng.close()
    return res, stats


def test_paged_decode_kernel_serves_the_reference_tokens(tiny, monkeypatch):
    """The Pallas paged decode kernel (interpreted) in the engine serves
    the same greedy tokens as the gather-then-attend reference; the block
    counters match a count by hand from each request's positions.  In
    float32: in bfloat16 the two round ``p·v`` differently (the kernel's
    probabilities are cast before they are normalised), and random
    weights' near-ties then flip a greedy token now and then."""
    import functools

    import repro.models.common as common

    cfg = _tiny_cfg(dtype="float32")
    params, _ = build_model(cfg).init(jax.random.PRNGKey(0))
    want, ref_stats = _serve_churn(cfg, params)
    monkeypatch.setattr(common, "paged_attend", functools.partial(
        common.paged_attend, use_pallas=True, interpret=True))
    got, stats = _serve_churn(cfg, params)
    assert stats.steps >= 64
    assert [r.token_ids for r in got] == [r.token_ids for r in want]
    # a request fed into s decode steps attends positions 0..L+j at step j
    m = MAX_LEN // BS
    read = sum(blocks_for(r.prompt_len + j + 1, BS)
               for r in got for j in range(r.steps))
    viewed = sum(r.steps for r in got) * m
    assert (stats.kv_blocks_read, stats.kv_blocks_viewed) == (read, viewed)
    assert (ref_stats.kv_blocks_read, ref_stats.kv_blocks_viewed) == (read, viewed)
    assert 0 < stats.kv_blocks_read < stats.kv_blocks_viewed


# ---------------------------------------------------------------------------
# continuous engine vs static engine
# ---------------------------------------------------------------------------

def test_uniform_batch_matches_static_engine(tiny, ref_engine):
    cfg, params = tiny
    cont = ContinuousEngine(
        cfg, params, _spec(),
        ServeConfig(max_new_tokens=20, max_len=MAX_LEN),
    )
    texts = ["InChI=1S/", "C6H12O6/c", "smiles:CC"]
    want = [r.token_ids for r in ref_engine.generate(texts)]
    got = [r.token_ids for r in cont.generate(texts)]
    assert got == want
    cont.close()


def test_ragged_budgets_match_serial(tiny, ref_engine):
    cfg, params = tiny
    cont = ContinuousEngine(
        cfg, params, _spec(),
        ServeConfig(max_new_tokens=20, max_len=MAX_LEN),
    )
    ragged = ["ab", "InChI=1S/C4H10/c1-3-4-2", "xy", "C1=CC=CC=C1O"]
    budgets = [3, 20, 5, 9]
    futs = [cont.submit(t, b, lead=False) for t, b in zip(ragged, budgets)]
    cont._maybe_lead()
    got = [f.result(timeout=300).token_ids for f in futs]
    for t, b, g in zip(ragged, budgets, got):
        assert g == ref_engine.generate([t])[0].token_ids[:b]
    # after drain the only blocks still resident are the prefix index's
    # published prompt blocks; clearing it must empty the pool exactly
    cont.check()
    st = cont._mgr.stats()
    held = sum(1 for _ in cont._index.block_refs()) if cont._index else 0
    assert st["in_use"] == held
    if cont._index is not None:
        cont._index.clear()
    st = cont._mgr.stats()
    assert st["in_use"] == 0 and st["allocs"] == st["frees"]
    cont._mgr.check()
    cont.close()


def test_pool_exhaustion_is_admission_backpressure(tiny, ref_engine):
    cfg, params = tiny
    # pool fits ONE long sequence at a time: 5 usable blocks, each
    # request needs 4 — the second must queue, then run after eviction
    cont = ContinuousEngine(
        cfg, params,
        _spec(n_blocks=6, max_slots=2, max_blocks_per_seq=4),
        ServeConfig(max_new_tokens=20, max_len=32),
    )
    texts = ["InChI=1S/C4", "C1=CC=CC=C1"]
    futs = [cont.submit(t, 20, lead=False) for t in texts]
    cont._maybe_lead()
    got = [f.result(timeout=300).token_ids for f in futs]
    assert cont.stats.admission_stalls > 0, "requests never contended"
    assert cont.stats.peak_active == 1
    # backpressure must not change bytes: compare against serial
    ref32 = Engine(cfg, params, ServeConfig(max_new_tokens=20, max_len=32))
    for t, g in zip(texts, got):
        assert g == ref32.generate([t])[0].token_ids
    cont._mgr.check()
    cont.close()


def test_admission_wait_counts_time_in_the_queue(tiny):
    """``queue_wait_s`` adds each admitted request's submit-to-admission
    time: with room for one sequence, the second waits out the first."""
    cfg, params = tiny
    cont = ContinuousEngine(
        cfg, params,
        _spec(n_blocks=6, max_slots=2, max_blocks_per_seq=4),
        ServeConfig(max_new_tokens=12, max_len=32),
    )
    futs = [cont.submit(t, 12, lead=False) for t in ("InChI=1S/C4", "C1=CC")]
    cont._maybe_lead()
    first, _ = [f.result(timeout=300) for f in futs]
    c = cont.counters()
    assert c["prefills"] == 2
    assert c["queue_wait_s"] >= first.prefill_s + first.decode_s
    cont.close()


def test_oversized_request_fails_cleanly(tiny):
    cfg, params = tiny
    cont = ContinuousEngine(
        cfg, params,
        _spec(n_blocks=4, max_slots=2, max_blocks_per_seq=4),
        ServeConfig(max_new_tokens=8, max_len=32),
    )
    # needs 4 blocks but only 3 usable exist: fails, doesn't hang/spin
    fut = cont.submit("InChI=1S/C8H9NO2/x", 13)
    with pytest.raises(RuntimeError, match="usable"):
        fut.result(timeout=60)
    # over the table width: rejected at submit
    fut2 = cont.submit("x" * 30, 8)
    with pytest.raises(ValueError, match="max_len"):
        fut2.result(timeout=60)
    # the engine still serves admissible requests afterwards
    r = cont.submit("ab", 4).result(timeout=300)
    assert len(r.token_ids) <= 4
    cont.close()


def test_concurrent_submits_and_slo(tiny, ref_engine):
    cfg, params = tiny
    cont = ContinuousEngine(
        cfg, params, _spec(),
        ServeConfig(max_new_tokens=20, max_len=MAX_LEN),
    )
    texts = ["InChI=1S/", "C6H12O6/c", "smiles:CC"] * 3
    outs = {}

    def worker(i, t):
        outs[i] = cont.submit(t, 8).result(timeout=300)

    ths = [threading.Thread(target=worker, args=(i, t))
           for i, t in enumerate(texts)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    for i, t in enumerate(texts):
        assert outs[i].token_ids == ref_engine.generate([t])[0].token_ids[:8]
    slo = cont.slo_ms()
    assert slo["ttft_p50_ms"] > 0 and slo["itl_p50_ms"] > 0
    assert cont.stats.completed == len(texts)
    c = cont.counters()
    assert c["tokens_out"] >= len(texts)  # counters are flat floats
    cont.close()
    with pytest.raises(RuntimeError, match="closed"):
        cont.submit("ab")


def test_unsupported_family_rejected(tiny):
    ssm_cfg = dataclasses.replace(
        get_config("mamba2-1.3b"),
        n_layers=2, d_model=64, vocab_size=300,
    )
    ssm_params, _ = build_model(ssm_cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged"):
        ContinuousEngine(ssm_cfg, ssm_params, _spec())


# ---------------------------------------------------------------------------
# static engine regression: ragged prompts
# ---------------------------------------------------------------------------

def test_static_engine_ragged_matches_serial(tiny, ref_engine):
    # the pad-logits regression: a ragged right-padded batch must start
    # every continuation from its OWN last prompt token, so batch output
    # equals per-prompt serial output
    texts = ["ab", "abcdefgh", "xyz", "InChI=1S/C8H9NO2/"]
    batched = ref_engine.generate(texts)
    for t, r in zip(texts, batched):
        assert r.token_ids == ref_engine.generate([t])[0].token_ids
