"""Serving engine + sharding-rule tests (incl. an 8-device subprocess)."""

import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist.logical import DEFAULT_RULES, divisible_spec
from repro.models.registry import build_model
from repro.serve.engine import Engine, ServeConfig


def _tiny_cfg(**kw):
    base = dataclasses.replace(
        get_config("yi-6b"),
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=300,
    )
    return dataclasses.replace(base, **kw)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def test_engine_greedy_deterministic_and_eos():
    cfg = _tiny_cfg()
    api = build_model(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(max_new_tokens=8, max_len=64))
    r1 = eng.generate(["InChI=1S/C4", "InChI=1S/C4"])
    assert r1[0].token_ids == r1[1].token_ids  # batch determinism
    r2 = eng.generate(["InChI=1S/C4"])
    assert r2[0].token_ids == r1[0].token_ids  # batch-size invariance
    assert all(len(r.token_ids) <= 8 for r in r1)


def test_engine_respects_prompt_lengths():
    cfg = _tiny_cfg()
    api = build_model(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(max_new_tokens=4, max_len=64))
    rs = eng.generate(["ab", "abcdef"])
    assert rs[0].prompt_len == 3 and rs[1].prompt_len == 7  # +BOS


# ---------------------------------------------------------------------------
# logical sharding rules
# ---------------------------------------------------------------------------

def test_rules_drop_missing_mesh_axes():
    # single-pod mesh has no "pod" axis: batch rule must degrade to data-only
    assert DEFAULT_RULES.mesh_axes("batch", ("data", "model")) == "data"
    assert DEFAULT_RULES.mesh_axes("batch", ("pod", "data", "model")) == (
        "pod", "data",
    )
    assert DEFAULT_RULES.mesh_axes("nonexistent", ("data", "model")) is None


def test_rules_no_duplicate_mesh_axis_in_spec():
    from jax.sharding import PartitionSpec as P

    class FakeMesh:
        axis_names = ("data", "model")

    spec = DEFAULT_RULES.spec(("d_ff", "vocab"), FakeMesh())  # both → model
    flat = [s for s in spec if s is not None]
    assert flat == ["model"] or flat == [("model",)] or len(flat) == 1


def test_divisible_spec_drops_uneven_axes():
    from jax.sharding import PartitionSpec as P

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    # 12 heads over model=16 → dropped; 32-dim over data=16 → kept
    out = divisible_spec(P("data", "model"), (32, 12), FakeMesh())
    assert tuple(out) == ("data", None)


# ---------------------------------------------------------------------------
# multi-device (8 fake CPU devices in a subprocess)
# ---------------------------------------------------------------------------

SUBPROCESS_PROG = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models.registry import build_model
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import batch_shardings, shardings_from_specs

    cfg = dataclasses.replace(
        get_config("qwen3-moe-235b-a22b").smoke(),
        n_layers=2, capacity_factor=8.0,
    )
    api = build_model(cfg)
    params, specs = api.init(jax.random.PRNGKey(0))
    B, S = 4, 32
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                     cfg.vocab_size),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }
    # single-device reference
    loss_ref, _ = jax.jit(api.loss)(params, batch)

    mesh = make_mesh((2, 4), ("data", "model"))
    with jax.set_mesh(mesh):
        psh = shardings_from_specs(mesh, specs, params)
        bsh = batch_shardings(mesh, {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                                     for k, v in batch.items()})
        params_s = jax.device_put(params, psh)
        batch_s = jax.device_put(batch, bsh)
        loss_sharded, _ = jax.jit(api.loss)(params_s, batch_s)
    out = {
        "ref": float(loss_ref),
        "sharded": float(loss_sharded),
        "n_dev": jax.device_count(),
    }
    print("RESULT:" + json.dumps(out))
    """
)


def test_moe_sharded_equals_single_device():
    """shard_map MoE on a 2×4 mesh reproduces the single-device loss."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_PROG],
        capture_output=True, text=True, env=env, timeout=500,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert out["n_dev"] == 8
    assert abs(out["ref"] - out["sharded"]) < 0.03, out


FLASH_MESH_PROG = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.launch.mesh import make_mesh
    from repro.models.common import attend

    rng = np.random.default_rng(0)
    mesh = make_mesh((2, 4), ("data", "model"))
    errs = {}
    for hkv in (4, 2):  # KV heads that divide the model axis, and not
        q = jnp.asarray(rng.standard_normal((2, 8, 40, 32)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, hkv, 40, 32)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, hkv, 40, 32)), jnp.float32)
        want = flash_attention_pallas(q, k, v, interpret=True)
        fn = jax.jit(lambda q, k, v: attend(
            q, k, v, use_pallas=True, interpret=True))
        with jax.set_mesh(mesh):
            got = fn(q, k, v)
        errs[hkv] = float(jnp.abs(got - want).max())
    print("RESULT:" + json.dumps(errs))
    """
)


def test_flash_kernel_under_mesh_matches_single_device():
    """The Mosaic kernel cannot be auto-partitioned: under a mesh it runs
    per device under shard_map, heads split over "model", and must give
    the unsharded result, GQA groups included."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", FLASH_MESH_PROG],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    errs = json.loads(line[len("RESULT:"):])
    assert max(errs.values()) < 1e-5, errs
