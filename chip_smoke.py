#!/usr/bin/env python3
"""Smoke run of the system's main paths on one TPU chip.

    python chip_smoke.py               # kernels, index tier, LM tier: 1 chip
    python chip_smoke.py --four-chips  # full-depth yi-6b on a 1x4 mesh
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny, on the CPU

Phases of the default run, all in this one process:

* kernels — each of the five Pallas kernels, called through the entry
  point the served paths call, compiles to the Mosaic kernel
  (``tpu_custom_call`` in the compiled program) and matches its reference.
* index — a generated SDF corpus (``RECORDS``, from ``--seed``) is
  indexed, published as a sharded store with fingerprint planes, and
  served through ``QueryService``: ``lookup_batch`` (device digest probe),
  ``similar`` (Tanimoto kernel) and ``fetch`` (``hash_mix`` digest
  verify), each compared byte for byte with the host path or the serial
  ``extract``; no key may come back degraded and no health domain may
  leave ``up``.
* lm — ``yi-6b`` at its published widths, depth cut to fit one chip,
  served by ``ContinuousEngine`` over the paged, prefix-sharing KV cache:
  ragged prompts, one longer than 512 tokens and not a multiple of 512,
  two sharing a block-aligned prefix.  Greedy tokens must be identical
  with the prefix cache on and off, and prefill plus four decode steps
  must match a plain float32 reference within ``LOGIT_REL_TOL``.

``--four-chips`` runs only the sharded path: full-depth ``yi-6b`` served
by the static ``Engine`` on a ``1x4`` ``("data", "model")`` mesh, then
the same parameters cut to the one-chip depth, prefilled on the mesh and
on one device, whose logits must agree within ``MESH_REL_TOL``.

Every timing printed is a smoke timing of this run, not a metric.  The
script refuses to run without a TPU (``--rehearse`` runs it on the CPU at
a tiny size, with the kernels in interpret mode), exits non-zero when any
phase fails, and only then prints, as its last line::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# bfloat16 serving vs the float32 reference: bf16 keeps 8 significant bits
# (unit roundoff 2^-9); a few roundings per layer over the cut depth gave a
# relative L2 logit error of 1.4-2.1 % on CPU at widths 128-1024 and 8
# layers, so 5 % bounds it with room and still catches a wrong mask,
# position or cache row (those move logits by O(100 %)).
LOGIT_REL_TOL = 0.05
# mesh vs one device, both bf16: only reduction order and the bf16
# rounding of partial sums differ, bounded like the above
MESH_REL_TOL = 0.05
# greedy tokens must follow the logits unless the top two are this close
# (relative to the largest logit): a near tie may flip between programs
NEAR_TIE = 0.02
# yi-6b layers whose float32 parameters (0.69 GB each, plus 2.1 GB of
# embedding and unembedding) fit one 16 GB v5e next to two 8-slot x 2048
# KV pools and the prefill temporaries; the same cut is the four-chip
# run's one-device comparison, which shares chip 0 with a mesh shard
ONE_CHIP_LAYERS = 8
SLOTS, MAX_LEN, BLOCK = 8, 2048, 16
NEW_TOKENS = 5  # first token from prefill, then four decode steps
PAPER_RECORDS = 176_929_690
# the generated corpus: ~415 MB at sdfgen's ~2 KB per record, built in
# about a minute; the rehearsal's is tiny
RECORDS, REHEARSE_RECORDS = 200_000, 4_000


class Clock:
    """Wall and compile seconds per phase (smoke timings, not metrics)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def phase(self, name, fn, *args):
        t0, c0, n0 = time.perf_counter(), self.compile_s, self.compiles
        out = fn(*args)
        print(
            f"smoke timing: phase {name}: wall {time.perf_counter() - t0:.1f} s, "
            f"compile {self.compile_s - c0:.1f} s over "
            f"{self.compiles - n0} compiles",
            flush=True,
        )
        return out


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def rel_err(got, want):
    """Relative L2 error of each row (float64)."""
    import numpy as np

    g = np.asarray(got, np.float64).reshape(len(want), -1)
    w = np.asarray(want, np.float64).reshape(len(want), -1)
    return np.linalg.norm(g - w, axis=1) / np.linalg.norm(w, axis=1)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def kernels_phase(rehearse: bool) -> None:
    """Each kernel through its public entry point: Mosaic on the chip,
    interpret mode in a rehearsal; results against the references."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    from repro.kernels.hash_mix.ops import hash_mix
    from repro.kernels.hash_mix.ref import hash_mix_ref
    from repro.kernels.sorted_probe.ops import sorted_probe
    from repro.kernels.sorted_probe.ref import sorted_probe_ref
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref
    from repro.kernels.tanimoto.kernel import tanimoto_blocks_pallas
    from repro.kernels.tanimoto.ops import tanimoto_topk
    from repro.kernels.tanimoto.ref import tanimoto_topk_ref

    rng = np.random.default_rng(0)
    kw = {"use_pallas": True, "interpret": True} if rehearse else {}

    def mosaic(name, jitted, *args, **static):
        if rehearse:
            return
        hlo = jitted.lower(*args, **static).compile().as_text()
        if "tpu_custom_call" not in hlo:
            raise AssertionError(f"{name}: compiled without the Mosaic kernel")

    table = np.unique(rng.integers(0, 2**32, (5000, 2), dtype=np.uint32), axis=0)
    qs = np.concatenate([table[::7], rng.integers(0, 2**32, (300, 2), np.uint32)])
    qs, table = jnp.asarray(qs), jnp.asarray(table)
    mosaic("sorted_probe", sorted_probe, qs, table)
    got = sorted_probe(qs, table, **kw)
    want = sorted_probe_ref(qs, table)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    ids = jnp.asarray(rng.integers(0, 2**32, (1000, 32), dtype=np.uint32))
    mosaic("hash_mix", hash_mix, ids)
    np.testing.assert_array_equal(
        np.asarray(hash_mix(ids, **kw)), np.asarray(hash_mix_ref(ids))
    )

    db = rng.integers(0, 2**32, (256, 32), dtype=np.uint32)
    q = db[:8] ^ rng.integers(0, 2**32, (8, 32), dtype=np.uint32) & np.uint32(
        0x01010101
    )
    mosaic(
        "tanimoto", tanimoto_blocks_pallas,
        jnp.asarray(db.T.copy()), jnp.zeros((1, 256), jnp.int32),
        jnp.asarray(q), jnp.zeros((8, 1), jnp.int32),
        block_d=256, k_pad=8, n_db=256,
    )
    got = tanimoto_topk(q, db, 8, interpret=rehearse)
    want = tanimoto_topk_ref(q, db, 8)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)

    qa = jnp.asarray(rng.standard_normal((1, 8, 200, 128)), jnp.bfloat16)
    ka = jnp.asarray(rng.standard_normal((1, 2, 200, 128)), jnp.bfloat16)
    va = jnp.asarray(rng.standard_normal((1, 2, 200, 128)), jnp.bfloat16)
    mosaic("flash_attention", jax.jit(flash_attention), qa, ka, va)
    np.testing.assert_allclose(
        np.asarray(flash_attention(qa, ka, va, **kw), np.float32),
        np.asarray(flash_attention_ref(qa, ka, va), np.float32),
        atol=3e-2,
    )

    states = jnp.asarray(rng.standard_normal((4, 6, 64, 128)), jnp.float32)
    decay = jnp.asarray(rng.uniform(0.2, 0.99, (4, 6)), jnp.float32)
    mosaic("ssd_scan", ssd_scan, states, decay)
    np.testing.assert_allclose(
        np.asarray(ssd_scan(states, decay, **kw)),
        np.asarray(ssd_scan_ref(states, decay)),
        atol=1e-5,
    )
    log("kernels: sorted_probe, hash_mix, tanimoto, flash_attention, "
        "ssd_scan match their references"
        + ("" if rehearse else " as Mosaic kernels (tpu_custom_call)"))


# ---------------------------------------------------------------------------
# index tier
# ---------------------------------------------------------------------------

def index_phase(rehearse: bool, seed: int, records: int, work: Path) -> None:
    import numpy as np

    from repro.core import RecordStore, build_index, extract
    from repro.core.fingerprint import DEFAULT_FP_BITS, fingerprint_batch
    from repro.core.sdfgen import CorpusSpec, generate_corpus
    from repro.core.store import IndexStore
    from repro.device import on_tpu
    from repro.service import QueryService, ServiceConfig

    n_files = 8
    spec = CorpusSpec(
        n_files=n_files, records_per_file=records // n_files,
        salt=f"chip-smoke-{seed}",
    )
    t0 = time.perf_counter()
    manifest = generate_corpus(work / "corpus", spec)
    rstore = RecordStore(work / "corpus")
    t1 = time.perf_counter()
    # one worker: building the index forks no pool from the process that
    # holds the chip
    idx = build_index(rstore, key_mode="full_id", workers=1)
    store_dir = work / "store"
    idx.save_sharded(store_dir, n_shards=16, fingerprint_bits=DEFAULT_FP_BITS)
    t2 = time.perf_counter()
    log(f"index cut: {spec.n_records:,} records "
        f"({manifest.total_bytes / 1e6:.0f} MB) of the paper's "
        f"{PAPER_RECORDS:,} ({spec.n_records / PAPER_RECORDS:.2%}); "
        f"16 shards, {DEFAULT_FP_BITS}-bit fingerprints")
    print(f"smoke timing: corpus {t1 - t0:.1f} s, index build+publish "
          f"{t2 - t1:.1f} s", flush=True)

    ref = IndexStore.open(store_dir)
    want_probe = "device" if on_tpu() else "host"
    if IndexStore._similar_probe(None) != want_probe:
        raise AssertionError("auto probe did not follow the device policy")
    keys = sorted(ref.iter_keys())
    rng = np.random.default_rng(seed)
    present = [keys[i] for i in rng.choice(len(keys), 2048, replace=False)]
    absent = [f"InChI=1S/absent/{seed}/{i}" for i in range(256)]
    sample = present + absent

    with QueryService(rstore, store_dir, ServiceConfig()) as svc:
        got = svc.lookup_batch(sample)
        want = ref.lookup_batch(sample, probe="host")
        for g, w in zip(got[:3], want):
            np.testing.assert_array_equal(g, w)
        if got.degraded.any() or int(got.hit.sum()) != len(present):
            raise AssertionError("lookup: degraded keys or wrong hit count")

        fps, _ = fingerprint_batch(present[:64], DEFAULT_FP_BITS)
        for n_q, k in ((64, 8), (8, 200)):  # batched top-8; a k past 128
            sim = svc.similar(fps[:n_q], k)
            sim_want = ref.similar_batch(fps[:n_q], k, probe="host")
            for g, w in zip(sim[:3], sim_want):
                np.testing.assert_array_equal(g, w)
            if sim[3].any():
                raise AssertionError(f"similar: degraded queries at k={k}")

        targets = present[:1500] + absent[:40]
        res = svc.fetch(targets)
        serial = extract(rstore, ref, targets, workers=0,
                         verify_backend="string")
        if (list(res.records.items()) != list(serial.records.items())
                or res.missing != serial.missing
                or res.mismatches != serial.mismatches):
            raise AssertionError("fetch differs from the serial extract")
        verified = svc.stats()["read"]["verify_records"]
        if verified < len(present[:1500]):
            raise AssertionError(f"fetch verified only {verified} records")
        if svc.router.health.has_unhealthy():
            raise AssertionError("a health domain left 'up'")
    log(f"index: {len(sample)} lookups, 64 similarity queries (top-8), "
        f"8 (top-200) and {len(targets)} fetches match the host path / serial "
        f"extract byte for byte on the {want_probe} path; no degraded key, "
        f"all domains up")


# ---------------------------------------------------------------------------
# LM tier
# ---------------------------------------------------------------------------

def _prompts(seed: int):
    """Ragged prompts: one over 512 tokens and not a multiple of 512, two
    sharing a 256-character (block-aligned with BOS: 16 full blocks) stem."""
    import numpy as np

    rng = np.random.default_rng(seed)
    text = lambda n: "".join(chr(c) for c in rng.integers(33, 127, n))
    stem = text(BLOCK * 16 - 1)  # + BOS = 16 full blocks
    return [text(37), text(1030), stem + text(20), stem + text(45), text(300)]


def served_logits(api, params, spec, prompt, fed):
    """Logits of the served path, teacher-forced: prefill at the engine's
    block bucket, paged write, then one paged decode step per fed token —
    the jitted model entry points ``ContinuousEngine`` runs, at its shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.tokenizer import ByteTokenizer
    from repro.serve.kvcache import BlockManager, blocks_for

    bs = spec.block_size
    n = len(prompt)
    toks = np.full((1, blocks_for(n, bs) * bs), ByteTokenizer.pad_id, np.int32)
    toks[0, :n] = prompt
    prefill = jax.jit(lambda p, b: api.prefill(p, b, max_len=spec.max_len))
    logits, dense = prefill(
        params, {"tokens": jnp.asarray(toks), "lengths": jnp.asarray([n])}
    )
    mgr = BlockManager(spec)
    if not mgr.admit(0, n + len(fed)):
        raise AssertionError("mirror could not admit its sequence")
    tables = jnp.asarray(mgr.tables)
    cache, _ = api.paged_cache_init(spec.n_blocks, bs)
    cache = jax.jit(lambda c, d, r: api.paged_prefill_write(c, d, r, bs))(
        cache, dense, tables[0]
    )
    step = jax.jit(
        lambda p, cur, pos, t, c: api.decode_step_paged(p, cur, pos, t, c, bs)
    )
    out = [np.asarray(logits[0], np.float32)]
    cur = np.zeros((spec.max_slots, 1), np.int32)
    pos = np.zeros((spec.max_slots,), np.int32)
    for i, tok in enumerate(fed):
        cur[0, 0], pos[0] = tok, n + i
        lg, cache = step(params, jnp.asarray(cur), jnp.asarray(pos), tables, cache)
        out.append(np.asarray(lg[0], np.float32))
    return np.stack(out)


def lm_phase(rehearse: bool, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.data.tokenizer import ByteTokenizer
    from repro.models.reference import dense_lm_logits
    from repro.models.registry import build_model
    from repro.serve.engine import ServeConfig
    from repro.serve.kvcache import PagedCacheSpec
    from repro.serve.scheduler import ContinuousEngine

    full = get_config("yi-6b")
    cfg = dataclasses.replace(
        full.smoke() if rehearse else full,
        n_layers=2 if rehearse else ONE_CHIP_LAYERS,
    )
    log(f"lm cut: yi-6b depth {cfg.n_layers} of {full.n_layers} layers at "
        f"d_model {cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; float32 params, "
        f"{cfg.dtype} compute; KV pool {SLOTS} slots x {MAX_LEN} tokens")
    api = build_model(cfg)
    params = jax.jit(lambda k: api.init(k)[0])(jax.random.PRNGKey(seed))
    m = MAX_LEN // BLOCK
    spec = PagedCacheSpec(
        n_blocks=SLOTS * m + 1, block_size=BLOCK, max_slots=SLOTS,
        max_blocks_per_seq=m,
    )
    scfg = ServeConfig(max_new_tokens=NEW_TOKENS, max_len=MAX_LEN)
    prompts = _prompts(seed)

    outs = {}
    for sharing in (True, False):
        with ContinuousEngine(cfg, params, spec, scfg, prefix_cache=sharing) as eng:
            outs[sharing] = [r.token_ids for r in eng.generate(prompts)]
            eng.check()
            if sharing and eng.stats.prefix_hits < 1:
                raise AssertionError("shared stem did not hit the prefix cache")
    if outs[True] != outs[False]:
        raise AssertionError(
            f"greedy tokens differ with the prefix cache on and off: "
            f"{outs[True]} vs {outs[False]}"
        )
    if any(len(t) != NEW_TOKENS for t in outs[True]):
        raise AssertionError(f"short generations: {outs[True]}")
    log(f"lm: {len(prompts)} ragged requests (prompt tokens "
        f"{[len(p) + 1 for p in prompts]}) served; greedy tokens identical "
        f"with the prefix cache on and off")

    tok = ByteTokenizer()
    ref_fn = jax.jit(lambda p, t, r: dense_lm_logits(p, cfg, t, r))
    for i in (1, 0):  # the >512-token prompt, then a short one
        prompt = tok.encode(prompts[i], add_eos=False)
        gen = outs[True][i]
        served = served_logits(api, params, spec, prompt, gen[: NEW_TOKENS - 1])
        seq = jnp.asarray(prompt + gen[: NEW_TOKENS - 1], jnp.int32)
        rows = jnp.arange(len(prompt) - 1, len(seq))
        ref = np.asarray(ref_fn(params, seq, rows))
        err = rel_err(served, ref)
        if not np.isfinite(served).all() or err.max() > LOGIT_REL_TOL:
            raise AssertionError(
                f"prompt {i}: logits vs float32 reference, relative L2 "
                f"{err.tolist()} > {LOGIT_REL_TOL}"
            )
        top2 = np.sort(served, axis=1)[:, -2:]
        tie = (top2[:, 1] - top2[:, 0]) <= NEAR_TIE * np.abs(served).max(1)
        follow = served.argmax(1) == np.asarray(gen)
        if not (follow | tie).all():
            raise AssertionError(
                f"prompt {i}: engine tokens {gen} do not follow the served "
                f"logits' argmax {served.argmax(1).tolist()}"
            )
        log(f"lm: prompt {i} ({len(prompt)} tokens) prefill + "
            f"{NEW_TOKENS - 1} decode steps vs float32 reference: relative L2 "
            f"{[float(f'{e:.3g}') for e in err]} (tolerance {LOGIT_REL_TOL})")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chip_phase(rehearse: bool, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.data.tokenizer import ByteTokenizer
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import shardings_from_specs
    from repro.models.registry import build_model
    from repro.serve.engine import Engine, ServeConfig

    if len(jax.devices()) != 4:
        raise AssertionError(
            f"--four-chips needs 4 devices, found {len(jax.devices())}"
        )
    mesh = make_mesh((1, 4), ("data", "model"))
    full = get_config("yi-6b")
    if rehearse:
        full = dataclasses.replace(full.smoke(), n_layers=4)
    cut = dataclasses.replace(full, n_layers=2 if rehearse else ONE_CHIP_LAYERS)
    prompts = _prompts(seed)[:2]
    tok = ByteTokenizer()

    def init_sharded(cfg):
        api = build_model(cfg)
        got = {}

        def init(k):
            p, got["specs"] = api.init(k)
            return p

        shapes = jax.eval_shape(init, jax.random.PRNGKey(seed))
        sh = shardings_from_specs(mesh, got["specs"], shapes)
        return jax.jit(init, out_shardings=sh)(jax.random.PRNGKey(seed)), got["specs"]

    def prefill_logits(cfg, params, on_mesh):
        api = build_model(cfg)
        ids = [tok.encode(p, add_eos=False) for p in prompts]
        toks = np.full((len(ids), max(map(len, ids))), tok.pad_id, np.int32)
        for r, p in enumerate(ids):
            toks[r, : len(p)] = p
        batch = {"tokens": jnp.asarray(toks),
                 "lengths": jnp.asarray([len(p) for p in ids], jnp.int32)}
        fn = jax.jit(lambda p, b: api.prefill(p, b, max_len=MAX_LEN)[0])
        if on_mesh:
            with jax.set_mesh(mesh):
                return np.asarray(fn(params, batch), np.float32)
        return np.asarray(fn(params, batch), np.float32)

    params, specs = init_sharded(full)
    log(f"four chips: yi-6b all {full.n_layers} layers, float32 params "
        f"sharded over a 1x4 ('data', 'model') mesh")
    eng = Engine(full, params, ServeConfig(max_new_tokens=4, max_len=MAX_LEN),
                 mesh=mesh, param_specs=specs)
    res = eng.generate(prompts)
    if any(len(r.token_ids) != 4 for r in res):
        raise AssertionError(f"short generations: {[r.token_ids for r in res]}")
    if not np.isfinite(prefill_logits(full, params, True)).all():
        raise AssertionError("full-depth logits are not finite")
    log(f"four chips: full depth served {len(prompts)} ragged requests "
        f"(prompt tokens {[r.prompt_len for r in res]}) on the mesh")
    del eng, res

    blocks = jax.tree_util.tree_map(lambda a: a[: cut.n_layers], params["blocks"])
    params = dict(params, blocks=blocks)
    on_mesh = prefill_logits(cut, params, True)
    one = jax.device_put(params, jax.devices()[0])
    del params, blocks
    on_one = prefill_logits(cut, one, False)
    err = rel_err(on_mesh, on_one)
    if err.max() > MESH_REL_TOL:
        raise AssertionError(
            f"mesh vs one device prefill logits, relative L2 {err.tolist()} "
            f"> {MESH_REL_TOL}"
        )
    log(f"four chips: depth-{cut.n_layers} cut, prefill logits on the mesh vs "
        f"one device: relative L2 {[float(f'{e:.3g}') for e in err]} "
        f"(tolerance {MESH_REL_TOL})")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus, prompts and parameters")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded full-depth LM path on 4 chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow the CPU: tiny widths, interpret-mode kernels")
    args = ap.parse_args()

    import jax

    from repro.device import use_compile_cache

    cache_dir = use_compile_cache()
    dev = jax.devices()
    platform, kind = dev[0].platform, dev[0].device_kind
    if platform != "tpu" and not args.rehearse:
        print(f"smoke: no TPU (JAX platform {platform!r}); refusing to run",
              file=sys.stderr)
        return 1
    log(f"device_kind {kind}, {len(dev)} devices, platform {platform}; "
        f"compile cache {cache_dir}")
    clock = Clock()
    work = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    try:
        if args.four_chips:
            clock.phase("four_chips", four_chip_phase, args.rehearse, args.seed)
        else:
            clock.phase("kernels", kernels_phase, args.rehearse)
            clock.phase("index", index_phase, args.rehearse, args.seed,
                        REHEARSE_RECORDS if args.rehearse else RECORDS, work)
            clock.phase("lm", lm_phase, args.rehearse, args.seed)
    except Exception:  # noqa: BLE001 — the script's boundary: report, fail
        traceback.print_exc()
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    stats = dev[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"device 0 peak_bytes_in_use {stats['peak_bytes_in_use']:,}")
    print(f"smoke timing: total compile {clock.compile_s:.1f} s over "
          f"{clock.compiles} compiles", flush=True)
    if args.rehearse:
        print("smoke: rehearsal passed (not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
