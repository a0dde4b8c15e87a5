"""repro.dist unit tests: logical rules, divisibility fallback, compression."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.compress import (
    ErrorFeedbackCompressor,
    dequantize_int8,
    make_compressor,
    quantize_int8,
    topk_mask,
)
from repro.launch.mesh import make_mesh
from repro.dist.logical import (
    DEFAULT_RULES,
    _current_mesh,
    axis_rules,
    constrain,
    current_rules,
    divisible_spec,
)


class FakeMesh:
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


# ---------------------------------------------------------------------------
# logical rules
# ---------------------------------------------------------------------------

def test_divisible_spec_replicates_non_divisible_dims():
    mesh = FakeMesh({"data": 16, "model": 16})
    out = divisible_spec(P("data", "model"), (32, 12), mesh)
    assert tuple(out) == ("data", None)
    # every dim uneven → fully replicated
    out = divisible_spec(P("data", "model"), (3, 5), mesh)
    assert tuple(out) == (None, None)


def test_divisible_spec_shrinks_axis_groups():
    # ("pod","data") = 2*16: 32 divides → whole group kept; 2 only fits "pod"
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    out = divisible_spec(P(("pod", "data"), None), (32, 7), mesh)
    assert tuple(out)[0] == ("pod", "data")
    out = divisible_spec(P(("pod", "data"), None), (2, 7), mesh)
    assert tuple(out)[0] == "pod"


def test_spec_consumes_each_mesh_axis_once():
    mesh = FakeMesh({"data": 2, "model": 4})
    spec = DEFAULT_RULES.spec(("batch", "heads", "kv_heads"), mesh)
    # heads takes "model"; kv_heads finds it consumed → replicated
    assert tuple(spec) == ("data", "model", None)


def test_axis_rules_override_and_restore():
    assert current_rules() is DEFAULT_RULES
    with axis_rules({"seq_sp": None, "custom": "model"}) as rules:
        assert current_rules() is rules
        assert rules.mesh_axes("seq_sp", ("data", "model")) is None
        assert rules.mesh_axes("custom", ("data", "model")) == "model"
        # untouched rules inherited from the default table
        assert rules.mesh_axes("heads", ("data", "model")) == "model"
    assert current_rules() is DEFAULT_RULES


def test_constrain_is_identity_without_mesh():
    assert _current_mesh() is None
    x = jnp.arange(12.0).reshape(3, 4)
    y = constrain(x, "batch", "d_ff")
    assert y is x  # literally a no-op, not a copy


def test_constrain_applies_under_mesh_and_preserves_values():
    mesh = make_mesh((1, 1), ("data", "model"))
    x = jnp.arange(8.0).reshape(2, 4)
    with jax.set_mesh(mesh):
        assert _current_mesh() is not None
        y = constrain(x, "batch", "d_ff")
        # jit path (how the models hit it)
        z = jax.jit(lambda a: constrain(a, "batch", "d_ff") * 2.0)(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(z), np.asarray(x) * 2.0)


def test_moe_honours_axis_rule_override():
    """axis_rules({'experts': None}) routes MoE through the local path."""
    from repro.configs import get_config
    from repro.models import moe

    cfg = dataclasses.replace(
        get_config("qwen3-moe-235b-a22b").smoke(),
        n_layers=1, capacity_factor=8.0,
    )
    from repro.models.common import compute_dtype

    params, _ = moe.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(
        jax.random.PRNGKey(1), (2, 8, cfg.d_model), compute_dtype(cfg)
    )
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        y_sharded, aux_sharded = moe.moe_apply(params, cfg, x)
        with axis_rules({"experts": None}):  # expert axis disabled → local
            y_local, aux_local = moe.moe_apply(params, cfg, x)
    np.testing.assert_allclose(
        np.asarray(y_sharded), np.asarray(y_local), atol=1e-5
    )
    np.testing.assert_allclose(
        float(aux_sharded), float(aux_local), atol=1e-6
    )


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_int8_round_trip_error_bound():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(64, 33)).astype(np.float32)) * 5.0
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s)
    assert q.dtype == jnp.int8 and back.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(back - x))) <= float(s) * 0.5 + 1e-6


def test_int8_zero_leaf_is_stable():
    q, s = quantize_int8(jnp.zeros((16,), jnp.float32))
    back = dequantize_int8(q, s)
    assert not bool(jnp.any(jnp.isnan(back)))
    np.testing.assert_array_equal(np.asarray(back), 0.0)


def test_int8_per_channel_beats_per_tensor_on_wide_variance():
    """Axis-0 scales bound each row's error by its OWN amax: on a leaf whose
    row magnitudes span 6 orders, per-tensor quantization flushes the small
    rows to zero while per-channel round-trips them."""
    rng = np.random.default_rng(11)
    rows = [rng.normal(size=48).astype(np.float32) * 10.0 ** (p - 4)
            for p in range(8)]
    x = jnp.asarray(np.stack(rows))
    q_pt, s_pt = quantize_int8(x)
    q_pc, s_pc = quantize_int8(x, per_channel=True)
    assert s_pc.shape == (8, 1)
    back_pt = dequantize_int8(q_pt, s_pt)
    back_pc = dequantize_int8(q_pc, s_pc)
    # per-channel error respects each row's own bound...
    row_err = jnp.max(jnp.abs(back_pc - x), axis=1)
    assert bool(jnp.all(row_err <= s_pc[:, 0] * 0.5 + 1e-9))
    # ...and is strictly better than per-tensor on the small rows
    small = jnp.abs(x[0])
    assert float(jnp.max(jnp.abs(back_pt[0] - x[0]))) >= float(jnp.max(small)) * 0.99
    assert float(jnp.max(jnp.abs(back_pc[0] - x[0]))) < float(jnp.max(small)) * 0.01
    assert float(jnp.sum(jnp.abs(back_pc - x))) < float(jnp.sum(jnp.abs(back_pt - x)))


def test_int8_per_channel_falls_back_on_vectors():
    x = jnp.asarray(np.linspace(-2, 2, 9, dtype=np.float32))
    q, s = quantize_int8(x, per_channel=True)
    assert s.ndim == 0  # per-tensor scalar scale for <2-dim leaves
    np.testing.assert_allclose(
        np.asarray(dequantize_int8(q, s)), np.asarray(x), atol=float(s) * 0.5 + 1e-6
    )


def test_topk_mask_keeps_largest():
    x = jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05], jnp.float32)
    out = np.asarray(topk_mask(x, 0.4))  # k = 2
    np.testing.assert_array_equal(out, [0.0, -5.0, 0.0, 3.0, 0.0])


@pytest.mark.parametrize(
    "method,per_channel", [("int8", False), ("int8", True), ("topk", False)]
)
def test_error_feedback_telescopes_to_true_gradient_sum(method, per_channel):
    comp = ErrorFeedbackCompressor(
        method=method, topk_frac=0.25, per_channel=per_channel
    )
    params = {"a": jnp.zeros((17,), jnp.float32), "n": {"b": jnp.zeros((4, 3))}}
    state = {"ef_residual": comp.init(params)}
    rng = np.random.default_rng(3)
    tot_true = {"a": np.zeros(17, np.float32), "b": np.zeros((4, 3), np.float32)}
    tot_comp = {"a": np.zeros(17, np.float32), "b": np.zeros((4, 3), np.float32)}
    for _ in range(40):
        g = {
            "a": jnp.asarray(rng.normal(size=17).astype(np.float32) * 1e-3),
            "n": {"b": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32))},
        }
        cg, state = comp.apply(g, state)
        tot_true["a"] += np.asarray(g["a"])
        tot_true["b"] += np.asarray(g["n"]["b"])
        tot_comp["a"] += np.asarray(cg["a"])
        tot_comp["b"] += np.asarray(cg["n"]["b"])
    res = state["ef_residual"]
    np.testing.assert_allclose(
        tot_comp["a"] + np.asarray(res["a"]), tot_true["a"], atol=1e-5
    )
    np.testing.assert_allclose(
        tot_comp["b"] + np.asarray(res["n"]["b"]), tot_true["b"], atol=1e-4
    )


def test_error_feedback_is_jit_compatible():
    comp = ErrorFeedbackCompressor()
    params = {"w": jnp.ones((8,), jnp.float32)}
    state = {"ef_residual": comp.init(params)}
    g = {"w": jnp.full((8,), 0.5, jnp.float32)}
    cg, new_state = jax.jit(comp.apply)(g, state)
    assert cg["w"].shape == (8,)
    assert "ef_residual" in new_state


def test_make_compressor_registry():
    assert make_compressor(None) is None
    assert make_compressor("none") is None
    assert make_compressor("int8_ef").method == "int8"
    pc = make_compressor("int8_pc_ef")
    assert pc.method == "int8" and pc.per_channel
    assert not make_compressor("int8_ef").per_channel
    tk = make_compressor("topk_ef", topk_frac=0.5)
    assert tk.method == "topk" and tk.topk_frac == 0.5
    with pytest.raises(ValueError):
        make_compressor("gzip")


def test_trainer_config_builds_compressor():
    from repro.train.trainer import TrainerConfig

    assert TrainerConfig().make_compressor() is None
    c = TrainerConfig(compress_grads=True, compressor="topk_ef", topk_frac=0.2)
    comp = c.make_compressor()
    assert comp.method == "topk" and comp.topk_frac == 0.2


# ---------------------------------------------------------------------------
# sharded serving (1×1 mesh on the CPU container: exercises the mesh path)
# ---------------------------------------------------------------------------

def test_engine_with_mesh_matches_unsharded():
    from repro.configs import get_config
    from repro.models.registry import build_model
    from repro.serve.engine import Engine, ServeConfig

    cfg = dataclasses.replace(
        get_config("yi-6b"),
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=300,
    )
    api = build_model(cfg)
    params, specs = api.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(max_new_tokens=6, max_len=64)
    ref = Engine(cfg, params, scfg).generate(["InChI=1S/C4"])
    mesh = make_mesh((1, 1), ("data", "model"))
    got = Engine(
        cfg, params, scfg, mesh=mesh, param_specs=specs
    ).generate(["InChI=1S/C4"])
    assert got[0].token_ids == ref[0].token_ids
