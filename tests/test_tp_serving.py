"""ContinuousEngine on a 1x4 tensor-parallel mesh (4 fake CPU devices in a
subprocess), and its programs without a mesh.

On seeded random weights the sharded engine's prefill and paged decode
logits match the plain float32 reference (``models/reference.py``); every
device holds one KV head of the pool and a quarter of every split matrix;
the embedding is looked up in each device's vocabulary slice with no
gather of the table; and without a mesh the engine lowers exactly the
programs it lowered before it took one.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.registry import build_model
from repro.serve.engine import ServeConfig
from repro.serve.kvcache import PagedCacheSpec
from repro.serve.scheduler import ContinuousEngine

# bfloat16 compute against the float32 reference: relative L2 error of a
# logits row stays near bf16's 2^-8 per op, compounded over 2 layers; 0.05
# is what chip_smoke.py holds the one-chip engine to (LOGIT_REL_TOL)
LOGIT_REL_TOL = 0.05

# yi-6b's block at a size whose query and KV heads both split four ways
TINY = dict(n_layers=2, d_model=128, n_heads=8, n_kv_heads=4, head_dim=16,
            d_ff=256, vocab_size=512)


def tiny_tp_cfg():
    return dataclasses.replace(get_config("yi-6b"), **TINY)


TP_PROG = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, json, re
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.data.tokenizer import ByteTokenizer
    from repro.launch.mesh import make_mesh
    from repro.models.common import embed_apply
    from repro.models.reference import dense_lm_logits
    from repro.models.registry import build_model
    from repro.serve.engine import ServeConfig, mesh_context
    from repro.serve.kvcache import BlockManager, PagedCacheSpec, blocks_for
    from repro.serve.scheduler import ContinuousEngine

    cfg = dataclasses.replace(get_config("yi-6b"),
                              **json.loads(os.environ["TINY_CFG"]))
    api = build_model(cfg)
    params, specs = api.init(jax.random.PRNGKey(7))
    mesh = make_mesh((1, 4), ("data", "model"))
    bs = 16
    spec = PagedCacheSpec(n_blocks=4 * 8 + 1, block_size=bs, max_slots=4,
                          max_blocks_per_seq=8)
    eng = ContinuousEngine(cfg, params, spec, ServeConfig(max_len=128),
                           mesh=mesh, param_specs=specs)
    tok = ByteTokenizer()
    texts = ["InChI=1S/C12H22O2/c1-3-5", "a longer prompt of some forty bytes."]
    served = [r.token_ids for r in eng.generate(texts, max_new_tokens=6)]

    # teacher-forced logits of the served path on the engine's own sharded
    # weights: its prefill and paged-write programs, then paged decode
    # steps on a pool laid out as its own
    ref_fn = jax.jit(lambda p, t, r: dense_lm_logits(p, cfg, t, r))
    step = jax.jit(lambda p, cur, pos, t, c: api.decode_step_paged(
        p, cur, pos, t, c, bs))
    out = {"prefill_err": [], "decode_err": [], "follows": []}
    for text, gen in zip(texts, served):
        prompt = tok.encode(text, add_eos=False)
        n = len(prompt)
        toks = np.full((1, blocks_for(n, bs) * bs), tok.pad_id, np.int32)
        toks[0, :n] = prompt
        mgr = BlockManager(spec)
        mgr.admit(0, n + len(gen))
        with mesh_context(mesh):
            logits, dense = eng._prefill(
                eng.params, {"tokens": eng._put(toks),
                             "lengths": eng._put(np.asarray([n], np.int32))})
            cache = jax.tree_util.tree_map(jnp.zeros_like, eng._cache)
            cache = eng._write(cache, dense, eng._put(mgr.tables[0]))
            got = [np.asarray(logits[0], np.float32)]
            cur = np.zeros((4, 1), np.int32)
            pos = np.zeros((4,), np.int32)
            for i, t in enumerate(gen[:-1]):
                cur[0, 0], pos[0] = t, n + i
                lg, cache = step(eng.params, eng._put(cur), eng._put(pos),
                                 eng._put(mgr.tables), cache)
                got.append(np.asarray(lg[0], np.float32))
        got = np.stack(got)
        seq = jnp.asarray(prompt + gen[:-1], jnp.int32)
        want = np.asarray(ref_fn(params, seq, jnp.arange(n - 1, len(seq))))
        err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
        out["prefill_err"].append(float(err[0]))
        out["decode_err"].append(float(err[1:].max()))
        # each served token is the argmax of its logits, or within a
        # bfloat16 rounding of it (random weights tie)
        top = got.max(axis=1)
        mine = got[np.arange(len(gen)), gen]
        out["follows"].append(bool((top - mine <= 1e-2 * np.abs(got).max()).all()))

    k = eng._cache["pos0"]["k"]
    out["pool_heads"] = sorted(
        (s.index[1].start, s.data.shape[1]) for s in k.addressable_shards)
    out["pool_shape"] = list(k.shape)
    quarter = {}
    for path, a in jax.tree_util.tree_leaves_with_path(eng.params):
        name = jax.tree_util.keystr(path)
        quarter[name] = [list(a.shape)] + sorted(
            {tuple(s.data.shape) for s in a.addressable_shards})
    out["shards"] = quarter

    # the vocabulary-local lookup: same rows as the replicated table's,
    # and no all-gather of the table in its program
    ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (3, 5)))
    lookup = jax.jit(lambda p, t: embed_apply(p, cfg, t))
    with mesh_context(mesh):
        rows = lookup(eng.params["embed"], eng._put(np.asarray(ids)))
        hlo = lookup.lower(eng.params["embed"], eng._put(np.asarray(ids))
                           ).compile().as_text()
    plain = lookup(params["embed"], ids)
    out["embed_err"] = float(jnp.abs(rows.astype(jnp.float32)
                                     - plain.astype(jnp.float32)).max())
    out["embed_gathers"] = len(re.findall(r" all-gather(?:-start)?\\(", hlo))
    out["embed_reduces"] = len(re.findall(r" all-reduce(?:-start)?\\(", hlo))
    out["place_s"] = eng.counters()["place_s"]

    # the paged decode kernel, interpreted, per device inside shard_map,
    # against the reference path through a churn of admissions and
    # evictions (float32: see test_continuous_batching)
    import functools
    import repro.models.common as common

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    churn = [("InChI=1S/C4H10/c1-3-4-2", 40), ("ab", 24), ("C1=CC=CC=C1O", 56),
             ("xylene", 30), ("InChI=1S/C8H9NO2/c1-6(10)9", 48), ("C6H6", 20),
             ("smiles:CC(=O)O", 44), ("InChI=1S/H2O/h1H2", 36), ("q", 28),
             ("ethanol", 40), ("C2H5OH", 33)]

    def serve():
        e = ContinuousEngine(cfg32, params, spec, ServeConfig(max_len=128),
                             mesh=mesh, param_specs=specs)
        futs = [e.submit(t, b, lead=False) for t, b in churn]
        e._maybe_lead()
        res = [f.result(timeout=600).token_ids for f in futs]
        return e, res

    ref_eng, want = serve()
    pools = []
    kernel = common.paged_decode

    def recorded(q, k_pool, *a, **kw):
        pools.append(list(k_pool.shape))
        return kernel(q, k_pool, *a, **kw)

    common.paged_decode = recorded
    common.paged_attend = functools.partial(
        common.paged_attend, use_pallas=True, interpret=True)
    ker_eng, got = serve()
    with mesh_context(mesh):
        hlo = ker_eng._step.lower(*ker_eng._step_inputs()).compile().as_text()
    out["kernel"] = {
        "same_tokens": got == want, "steps": ker_eng.stats.steps,
        "pools": pools,
        "blocks": [ker_eng.stats.kv_blocks_read, ker_eng.stats.kv_blocks_viewed],
        "ref_blocks": [ref_eng.stats.kv_blocks_read, ref_eng.stats.kv_blocks_viewed],
        "gathers": re.findall(r"= \\w+\\[([\\d,]*)\\][^ ]* all-gather(?:-start)?\\(", hlo),
    }
    print("RESULT:" + json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def tp_run():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    env["TINY_CFG"] = json.dumps(TINY)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", TP_PROG],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


def test_tp_engine_matches_reference_logits(tp_run):
    """Prefill, then decode through the paged cache, on the 1x4 mesh."""
    assert max(tp_run["prefill_err"]) < LOGIT_REL_TOL, tp_run["prefill_err"]
    assert max(tp_run["decode_err"]) < LOGIT_REL_TOL, tp_run["decode_err"]
    assert all(tp_run["follows"]), tp_run["follows"]


def test_tp_pool_holds_one_kv_head_per_device(tp_run):
    layers, hkv, rows, dh = tp_run["pool_shape"]
    assert hkv == 4
    assert tp_run["pool_heads"] == [[i, 1] for i in range(4)]


def test_tp_split_matrices_hold_a_quarter_per_device(tp_run):
    cfg = tiny_tp_cfg()
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    want = {
        "['blocks']['attn']['wq']": (d, h * dh // 4),
        "['blocks']['attn']['wk']": (d, hkv * dh // 4),
        "['blocks']['attn']['wv']": (d, hkv * dh // 4),
        "['blocks']['attn']['wo']": (h * dh // 4, d),
        "['blocks']['mlp']['wg']": (d, f // 4),
        "['blocks']['mlp']['wu']": (d, f // 4),
        "['blocks']['mlp']['wd']": (f // 4, d),
    }
    shards = tp_run["shards"]
    for name, shape in want.items():
        full, *local = shards[name]
        assert local == [[cfg.n_layers, *shape]], (name, full, local)
    assert shards["['embed']['table']"][1:] == [[v // 4, d]]
    assert shards["['embed']['unembed']"][1:] == [[d, v // 4]]
    # norms stay whole on every device
    assert shards["['final_norm']"][1:] == [[d]]


def test_tp_embedding_is_looked_up_where_the_vocabulary_lives(tp_run):
    assert tp_run["embed_err"] == 0.0
    assert tp_run["embed_gathers"] == 0
    assert tp_run["embed_reduces"] >= 1


def test_tp_step_inputs_are_placed_and_timed(tp_run):
    assert tp_run["place_s"] > 0


def test_tp_paged_decode_kernel_serves_the_reference_tokens(tp_run):
    """On the 1x4 mesh the interpreted paged decode kernel runs once per
    layer on each device's own KV head, serves the reference path's
    greedy tokens through admissions and evictions (>= 64 steps), and the
    step gathers nothing but the argmax's values."""
    k = tp_run["kernel"]
    assert k["same_tokens"] and k["steps"] >= 64
    cfg = tiny_tp_cfg()
    assert k["pools"] and all(p[:2] == [cfg.n_layers, 1] for p in k["pools"])
    assert k["blocks"] == k["ref_blocks"]
    assert 0 < k["blocks"][0] < k["blocks"][1]
    # the argmax's values: a few a slot on each of the 4 devices
    assert all(np.prod([int(d) for d in dims.split(",")]) <= 4 * 4
               for dims in k["gathers"]), k["gathers"]


# ---------------------------------------------------------------------------
# no mesh: the programs the engine lowered before it took one
# ---------------------------------------------------------------------------

def _before_mesh(api, spec):
    """The decode step and prefill as the engine jitted them without a mesh."""
    bs = spec.block_size

    def step(p, cur, pos, tables, cache):
        logits, cache = api.decode_step_paged(p, cur, pos, tables, cache, bs)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    return (jax.jit(step, donate_argnums=(4,)),
            jax.jit(lambda p, b: api.prefill(p, b, max_len=spec.max_len)))


@pytest.mark.parametrize("args", ["defaults", "mesh_none"])
def test_no_mesh_lowers_the_programs_of_before(args):
    cfg = tiny_tp_cfg()
    api = build_model(cfg)
    params, specs = api.init(jax.random.PRNGKey(0))
    spec = PagedCacheSpec(n_blocks=2 * 4 + 1, block_size=16, max_slots=2,
                          max_blocks_per_seq=4)
    extra = {} if args == "defaults" else {"mesh": None, "param_specs": specs}
    eng = ContinuousEngine(cfg, params, spec, ServeConfig(max_len=64), **extra)
    step, prefill = _before_mesh(api, spec)
    step_args = (params, jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32),
                 jnp.asarray(eng._mgr.tables), eng._cache)
    batch = {"tokens": jnp.zeros((1, 32), jnp.int32),
             "lengths": jnp.asarray([5], jnp.int32)}
    assert eng._step.lower(*step_args).as_text() == \
        step.lower(*step_args).as_text()
    assert eng._prefill.lower(params, batch).as_text() == \
        prefill.lower(params, batch).as_text()
    assert eng.counters()["place_s"] == 0.0
    np.testing.assert_array_equal(
        np.asarray(eng._put(np.arange(3))), np.arange(3))
