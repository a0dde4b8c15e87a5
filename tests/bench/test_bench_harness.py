"""The benchmark harness under ``bench/``, on the CPU at tiny sizes.

Every cell of ``BENCHMARK.json`` runs once with ``--rehearse`` (CPU, tiny
sizes, the same code path as on the chip) and must print one JSON result
line; a cell whose configuration, traffic mix, traffic kind and metric are
defined only by files of a temporary registry is found by name; without a
TPU every run outside ``--rehearse`` refuses.  The runs are in this
process, with JAX's cache settings put back afterwards
(``test_bench_faults.py`` plants faults and runs the controls).
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as bench_run  # noqa: E402
from bench import keygen, workload  # noqa: E402
from bench.registry import Registry, merged  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SEED = 3_000_000_019          # above 2**31, as the benchmark's seeds may be


@pytest.fixture
def harness(monkeypatch, tmp_path, capsys):
    """``run(workload, root=ROOT) -> (rc, result or None, stderr)``."""
    import jax

    keep = {k: getattr(jax.config, k) for k in
            ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")}
    # use_compile_cache() leaves the cache alone where this is set
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))

    def run(workload, root=ROOT, rehearse=True, seed=SEED, extra=()):
        argv = ["--workload", workload, "--seed", str(seed), "--seconds", "1",
                "--trace", "0"] + (["--rehearse"] if rehearse else []) + list(extra)
        rc = bench_run.main(argv, root=root)
        out = capsys.readouterr()
        lines = out.out.strip().splitlines()
        return rc, (json.loads(lines[-1]) if lines else None), out.err

    yield run
    for k, v in keep.items():
        jax.config.update(k, v)


def _ok(result, cell):
    assert RESULT_KEYS <= set(result)
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["kind"] and result["device"]["count"] == 1
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    # the numbers compared, each with its limit, come last
    assert list(result)[-1] == "checks"
    assert all({"value", "limit"} == set(c) for c in result["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_rehearses(harness, workload):
    rc, result, err = harness(workload)
    assert rc == 0, err
    _ok(result, Registry(ROOT).cell(workload))
    assert result["correct"] is True, result["checks"]
    # the last lines of standard error are the numbers compared
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") for line in tail)


def test_no_tpu_refuses(harness):
    rc, result, err = harness(CELLS[0], rehearse=False)
    assert rc != 0 and result is None
    assert "no TPU" in err


def test_cell_found_by_name_from_files_alone(harness, tmp_path):
    """A configuration, a traffic mix, a traffic kind and a per-layer
    metric that exist only as files of their own (and entries in
    BENCHMARK.json) are picked up; everything is read from the checkout
    the registry is given."""
    root = tmp_path / "checkout"
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic" / "kinds").mkdir(parents=True)
    (root / "bench" / "metrics").mkdir()
    shutil.copytree(ROOT / "bench/systems", root / "bench/systems")
    conf = json.loads((ROOT / "bench/configs/pubchem-index.json").read_text())
    conf["n_shards"] = 4
    conf["reference"] = "tiny-index.reference.py"
    (root / "bench/configs/tiny-index.json").write_text(json.dumps(conf))
    shutil.copy(ROOT / "bench/configs/pubchem-index.reference.py",
                root / "bench/configs/tiny-index.reference.py")
    # a new kind: the point-lookup kind under a name of its own
    shutil.copy(ROOT / "bench/traffic/kinds/point_lookup.py",
                root / "bench/traffic/kinds/tiny_lookup.py")
    (root / "bench/traffic/tiny-mix.json").write_text(json.dumps(
        {"kind": "tiny_lookup", "clients": 3, "keys_per_request": 2,
         "zipf_theta": 0.5, "absent_share": 0.3, "requests_per_client": 64}))
    (root / "bench/metrics/tiny_requests.py").write_text(
        "def read(ctx):\n    return float(ctx.system.requests_in_window(ctx.state))\n")
    spec = {
        "command": SPEC["command"], "paths": SPEC["paths"], "run_seconds": 1,
        "configs": [{"name": "tiny-index", "source": "test",
                     "file": "bench/configs/tiny-index.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny-index.tiny-mix", "config": "tiny-index",
                       "traffic": "tiny-mix", "chips": 1, "why": "test"}],
        "end_to_end": [m for m in SPEC["end_to_end"]
                       if m["name"] in ("requests_per_s", "setup_s")],
        "per_layer": [{"name": "tiny_requests", "unit": "req", "better": "higher",
                       "source": "host_clock", "layer": "test",
                       "moves": "requests_per_s"}],
    }
    for m in spec["end_to_end"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    reg = Registry(root)
    cell = reg.cell("tiny-index.tiny-mix")
    assert cell.config["n_shards"] == 4 and cell.traffic["clients"] == 3
    assert [m["name"] for m in cell.per_layer] == ["tiny_requests"]
    assert reg.reader("tiny_requests").read is not None
    assert Path(reg.kind(cell).__file__) == root / "bench/traffic/kinds/tiny_lookup.py"
    assert Path(reg.reference(cell).__file__).parent == root / "bench/configs"
    rc, result, err = harness("tiny-index.tiny-mix", root=root)
    assert rc == 0, err
    _ok(result, cell)
    assert result["correct"] is True


# -- the generators ---------------------------------------------------------


def test_keys_are_distinct_seeded_and_inchi_sized():
    a = keygen.make_keys(np.arange(5000), 7)
    assert a == keygen.make_keys(np.arange(5000), 7)
    assert a != keygen.make_keys(np.arange(5000), 8)
    assert len(set(a)) == len(a)
    assert all(k.startswith("InChI=1S/C") for k in a)
    lens = np.array([len(k) for k in a])
    # the program's canonical_id: mean about 293 bytes, 186-402 at 5-95 %
    assert 260 < lens.mean() < 330
    assert 150 < np.percentile(lens, 5) and np.percentile(lens, 95) < 440
    assert len(set(keygen.digests(a).tolist())) == len(a)


def test_streams_are_seeded_and_chat_sizes_fixed():
    reg = Registry(ROOT)
    cell = reg.cell("yi-6b-1chip.chat")
    chat, kind = cell.traffic, reg.kind(cell)
    s1 = workload.streams(kind, chat, {}, 1)
    s2 = workload.streams(kind, chat, {}, 2)
    assert all(np.array_equal(x, y)
               for x, y in zip(s1, workload.streams(kind, chat, {}, 1)))
    # every seed serves the same set of prompt and output lengths
    cat = lambda s: np.concatenate(s)  # noqa: E731
    assert sorted(cat(s1)[:, 0]) == sorted(cat(s2)[:, 0])
    assert sorted(cat(s1)[:, 1]) == sorted(cat(s2)[:, 1])
    assert not np.array_equal(cat(s1)[:, 0], cat(s2)[:, 0])
    lo, hi = chat["prompt_tokens"]["lo"], chat["prompt_tokens"]["hi"]
    assert cat(s1)[:, 0].min() >= lo and cat(s1)[:, 0].max() <= hi

    cell = reg.cell("pubchem-index.lookup-zipf")
    zipf = cell.traffic
    sizes = {"n_present": 10_000, "n_absent": 500}
    keys = np.concatenate(workload.streams(reg.kind(cell), zipf, sizes, 5)).ravel()
    absent = keys >= sizes["n_present"]
    assert abs(absent.mean() - zipf["absent_share"]) < 0.01
    # skew: the hottest key draws far more than a uniform share
    top = np.bincount(keys[~absent]).max() / (~absent).sum()
    assert top > 50 / sizes["n_present"]


def test_rehearse_overrides_merge_key_by_key():
    assert merged({"a": 1, "m": {"x": 1, "y": 2}}, {"m": {"y": 3}, "b": 4}) \
        == {"a": 1, "m": {"x": 1, "y": 3}, "b": 4}
    full = Registry(ROOT).cell("yi-6b-1chip.chat")
    tiny = Registry(ROOT, rehearse=True).cell("yi-6b-1chip.chat")
    # a nested group keeps the keys its override leaves out
    assert tiny.config["model"]["d_model"] == full.config["rehearse"]["model"]["d_model"]
    assert tiny.config["model"]["rope_theta"] == full.config["model"]["rope_theta"]
    assert tiny.config["paged"]["block_size"] == full.config["paged"]["block_size"]
    assert tiny.traffic["clients"] == full.traffic["rehearse"]["clients"]


def test_ttft_is_timed_from_the_first_token_on_the_host(monkeypatch):
    """The chat cell's TTFT is the client's completion time less the
    engine's ``GenerationResult.decode_s``.  That is the first token's
    time only while ``decode_s`` runs from the moment the first token
    reached the host (after the prefill's device sync); this pins it."""
    import argparse

    import jax

    from repro.serve.scheduler import ContinuousEngine

    seen = {}
    admit, first = ContinuousEngine._admit_one, ContinuousEngine._first_token

    def admit_one(self, req, *a, **kw):
        self._bench_prompt = tuple(req.prompt)
        return admit(self, req, *a, **kw)

    def first_token(self, logits, seed):
        tok = first(self, logits, seed)       # syncs: the token is on the host
        seen.setdefault(self._bench_prompt, []).append(time.perf_counter())
        return tok

    monkeypatch.setattr(ContinuousEngine, "_admit_one", admit_one)
    monkeypatch.setattr(ContinuousEngine, "_first_token", first_token)
    reg = Registry(ROOT, rehearse=True)
    cell = reg.cell("yi-6b-1chip.chat")
    system = reg.system(cell)
    ctx = bench_run.Context(cell, argparse.Namespace(seed=SEED, seconds=1.0, trace=0),
                            None, jax.devices()[:1], True)
    ctx.system, ctx.kind, ctx.reference = system, reg.kind(cell), reg.reference(cell)
    state = system.setup(ctx)
    try:
        system.measure(ctx, state)
    finally:
        system.free(state)
    done = [r for r in state.window.records if r.error is None]
    assert done
    for r in done:
        arrivals = seen[tuple(system.prompt_ids(state, r))]
        lag = min(r.answer.t_first - t for t in arrivals if t <= r.answer.t_first)
        assert r.t_send <= r.answer.t_first <= r.t_done
        # the engine stamps the first token right after it reaches the host
        assert 0.0 <= lag < 0.05
