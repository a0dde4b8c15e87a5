"""Per-architecture smoke tests (deliverable f).

Each assigned architecture instantiates its REDUCED same-family config and
runs one forward/train step on CPU, asserting output shapes and no NaNs;
plus decode-vs-forward consistency on representative families.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.models.common import unembed_logits
from repro.models.registry import build_model


def _batch_for(cfg, B=2, S=64, key=jax.random.PRNGKey(1)):
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.enc_frames, cfg.d_model)
        )
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.n_img_tokens, cfg.d_model)
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_forward_loss_no_nans(arch):
    cfg = get_config(arch).smoke()
    api = build_model(cfg)
    params, specs = api.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg)
    loss, metrics = jax.jit(api.loss)(params, batch)
    assert loss.shape == ()
    assert not bool(jnp.isnan(loss)), f"{arch} produced NaN loss"
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_train_step_updates_params(arch):
    from repro.train.loop import make_train_state, make_train_step
    from repro.train.optimizer import AdamWConfig

    cfg = get_config(arch).smoke()
    api = build_model(cfg)
    state = make_train_state(api, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(api, AdamWConfig(warmup_steps=1)))
    batch = _batch_for(cfg)
    new_state, m = step(state, batch)
    assert not bool(jnp.isnan(m["loss"]))
    assert int(new_state["step"]) == 1
    # at least one parameter moved
    moved = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.any(a != b)), state["params"], new_state["params"]
    )
    assert any(jax.tree_util.tree_leaves(moved)), f"{arch}: no param moved"


@pytest.mark.parametrize(
    "arch", ["yi-6b", "gemma3-12b", "mamba2-1.3b", "jamba-1.5-large-398b",
             "whisper-small", "qwen3-moe-235b-a22b"]
)
def test_smoke_decode_matches_forward(arch):
    cfg = get_config(arch).smoke()
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # no train drops
    api = build_model(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    B, S = 2, 64
    batch = _batch_for(cfg, B, S)
    nimg = cfg.n_img_tokens or 0
    logits_pre, cache = jax.jit(
        lambda p, b: api.prefill(p, b, max_len=S + nimg + 4)
    )(params, batch)
    tok = jnp.argmax(logits_pre, -1)[:, None].astype(jnp.int32)
    pos = jnp.full((B,), S + nimg, jnp.int32)
    logits_dec, _ = jax.jit(api.decode_step)(params, tok, pos, cache)

    ext = dict(batch)
    ext["tokens"] = jnp.concatenate([batch["tokens"], tok], axis=1)
    ext["loss_mask"] = jnp.ones_like(ext["tokens"], jnp.float32)
    if cfg.family in ("dense", "moe", "vlm"):
        from repro.models.transformer import lm_forward

        hid, _ = lm_forward(params, cfg, ext["tokens"], ext.get("patch_embeds"))
    elif cfg.family == "hybrid":
        from repro.models.hybrid import hybrid_forward

        hid, _ = hybrid_forward(params, cfg, ext["tokens"])
    elif cfg.family == "ssm":
        from repro.models.ssm import ssm_forward

        hid, _ = ssm_forward(params, cfg, ext["tokens"])
    else:
        from repro.models.encdec import encdec_forward

        hid = encdec_forward(params, cfg, ext["frames"], ext["tokens"])
    truth = unembed_logits(params["embed"], cfg, hid[:, -1:, :])[:, 0]
    err = float(jnp.max(jnp.abs(
        logits_dec.astype(jnp.float32) - truth.astype(jnp.float32)
    )))
    assert err < 0.06, f"{arch}: decode/forward divergence {err}"


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 0.05)])
def test_prefill_matches_float32_reference(dtype, tol):
    """The served prefill of a ragged row agrees with the independent
    float32 reference (``models/reference.py``): tightly when the model
    computes in float32, within bf16 rounding when it computes in bf16."""
    from repro.models.reference import dense_lm_logits

    cfg = dataclasses.replace(get_config("yi-6b").smoke(), n_layers=2,
                              dtype=dtype)
    api = build_model(cfg)
    params, _ = api.init(jax.random.PRNGKey(3))
    n, width = 37, 48  # a ragged row: 11 pad positions after it
    toks = jax.random.randint(jax.random.PRNGKey(4), (n,), 0, cfg.vocab_size)
    batch = {"tokens": jnp.zeros((1, width), jnp.int32).at[0, :n].set(toks),
             "lengths": jnp.asarray([n], jnp.int32)}
    got, _ = jax.jit(lambda p, b: api.prefill(p, b, max_len=64))(params, batch)
    want = jax.jit(lambda p, t: dense_lm_logits(p, cfg, t, jnp.asarray([n - 1])))(
        params, toks
    )
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < tol, f"{dtype}: relative L2 {err}"


def test_full_configs_match_assignment():
    """The published numbers, verbatim (guards accidental edits)."""
    rows = {
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "yi-6b": (32, 4096, 32, 4, 11008, 64000),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "qwen1.5-110b": (80, 8192, 64, 8, 49152, 152064),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "moonshot-v1-16b-a3b": (48, 2048, 16, 16, 1408, 163840),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "mamba2-1.3b": (48, 2048, 1, 1, 0, 50280),
        "whisper-small": (12, 768, 12, 12, 3072, 51865),
        "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
    }
    for arch, (L, D, H, KV, F, V) in rows.items():
        c = get_config(arch)
        assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
                c.vocab_size) == (L, D, H, KV, F, V), arch
    # MoE / structural details
    assert get_config("qwen3-moe-235b-a22b").n_experts == 128
    assert get_config("qwen3-moe-235b-a22b").experts_per_token == 8
    assert get_config("moonshot-v1-16b-a3b").n_experts == 64
    assert get_config("moonshot-v1-16b-a3b").experts_per_token == 6
    assert get_config("jamba-1.5-large-398b").n_experts == 16
    assert get_config("jamba-1.5-large-398b").hybrid_block == 8
    assert get_config("gemma3-12b").local_block == 6
    assert get_config("mamba2-1.3b").ssm_state == 128
    assert get_config("qwen2-72b").qkv_bias


PUBLISHED_PARAMS = {
    "qwen2-72b": (65e9, 85e9),
    "yi-6b": (5.5e9, 7e9),
    "gemma3-12b": (10e9, 15e9),
    "qwen1.5-110b": (100e9, 125e9),
    "jamba-1.5-large-398b": (350e9, 440e9),
    "qwen3-moe-235b-a22b": (210e9, 260e9),
    # the ASSIGNED config (48L × 64e × d_ff 1408) arithmetically implies
    # ~28B total; the real Moonlight-16B has 27 layers — we implement
    # the assignment as specified
    "moonshot-v1-16b-a3b": (24e9, 32e9),
    "mamba2-1.3b": (1.0e9, 1.7e9),
    "whisper-small": (0.2e9, 0.5e9),
    "internvl2-76b": (66e9, 86e9),
}


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_full_param_counts_in_published_ballpark(arch):
    """Abstract init (no allocation) → param totals ≈ the model names."""
    from repro.launch.sharding import abstract
    from repro.models.common import param_count

    lo, hi = PUBLISHED_PARAMS[arch]
    api = build_model(get_config(arch))
    params, _ = abstract(api.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    n = param_count(params)
    assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]"


def test_shape_cells_runnable_map():
    from repro.configs import cell_is_runnable, runnable_cells

    cells = runnable_cells()
    assert len(cells) == 33  # 10×4 minus 7 long_500k skips
    assert ("mamba2-1.3b", "long_500k") in cells
    assert ("jamba-1.5-large-398b", "long_500k") in cells
    assert ("gemma3-12b", "long_500k") in cells
    assert ("qwen2-72b", "long_500k") not in cells
