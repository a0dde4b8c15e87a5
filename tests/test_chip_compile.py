"""Compile each Pallas kernel for a described TPU v5e chip.

Interpret mode (``tests/test_kernels.py``) checks what a kernel computes;
it cannot see what the Mosaic compiler refuses — blocks that break the
(8, 128) tiling rule, scoped VMEM overruns, unsupported relayouts.  These
tests ask the installed TPU compiler to compile every kernel at the
widths the serving paths use, for a ``v5e:2x2`` topology that is
described, not attached, and check that the compiled program holds the
Mosaic kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and test
workers import every test file.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile_for_chip(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, "compiled without the Mosaic kernel"


@pytest.mark.parametrize("q,m", [(4096, 2**20), (64, 12_500)])
def test_sorted_probe_compiles(one_chip, q, m):
    from repro.kernels.sorted_probe.ops import sorted_probe_pallas

    _compile_for_chip(
        sorted_probe_pallas,
        ((q, 2), jnp.uint32), ((m, 2), jnp.uint32),
        sharding=one_chip,
    )


@pytest.mark.parametrize("n,w", [(1024, 32), (8192, 32), (300, 64)])
def test_hash_mix_compiles(one_chip, n, w):
    from repro.kernels.hash_mix.kernel import hash_mix_pallas

    _compile_for_chip(hash_mix_pallas, ((n, w), jnp.uint32), sharding=one_chip)


@pytest.mark.parametrize("q,rows,w,k", [(64, 32768, 32, 8), (8, 200, 32, 32),
                                         (8, 1000, 32, 200)])
def test_tanimoto_compiles(one_chip, q, rows, w, k):
    from repro.kernels.tanimoto.kernel import tanimoto_blocks_pallas

    block_d = min(256, -(-rows // 128) * 128)
    d_pad = -(-rows // block_d) * block_d
    fn = functools.partial(
        tanimoto_blocks_pallas, block_d=block_d, k_pad=k, n_db=rows
    )
    _compile_for_chip(
        fn,
        ((w, d_pad), jnp.uint32), ((1, d_pad), jnp.int32),
        ((q, w), jnp.uint32), ((q, 1), jnp.int32),
        sharding=one_chip,
    )


def test_ssd_scan_compiles(one_chip):
    from repro.kernels.ssd_scan.kernel import ssd_scan_pallas

    _compile_for_chip(
        ssd_scan_pallas,
        ((64, 16, 64, 128), jnp.float32), ((64, 16), jnp.float32),
        sharding=one_chip,
    )


# yi-6b attention widths: 32 query heads over 4 KV heads, head dim 128;
# full prefills at 2048 and 1040 (not a block multiple), suffix prefills
@pytest.mark.parametrize("sq,skv", [(2048, 2048), (1040, 1040), (32, 48),
                                    (48, 1040)])
def test_flash_attention_compiles(one_chip, sq, skv):
    from repro.kernels.flash_attention.kernel import flash_attention_pallas

    _compile_for_chip(
        flash_attention_pallas,
        ((1, 32, sq, 128), jnp.bfloat16),
        ((1, 4, skv, 128), jnp.bfloat16),
        ((1, 4, skv, 128), jnp.bfloat16),
        sharding=one_chip,
    )


def test_tp_decode_step_compiles_for_four_chips(topo):
    """Yi-6B whole, the continuous engine's paged decode step on a 1x4
    mesh of the described chips: it fits a chip, and the only all-gather
    in it is the argmax's (a few values a slot) — no weight, embedding
    table or KV pool moves between chips; the per-layer combines are
    all-reduces of the (64, 1, 4096) activations."""
    import re

    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.configs.base import ModelConfig
    from repro.launch.sharding import abstract, shardings_from_specs
    from repro.models.registry import build_model

    cfg = ModelConfig(name="yi-6b-full", family="dense", n_layers=32,
                      d_model=4096, n_heads=32, n_kv_heads=4, head_dim=128,
                      d_ff=11008, vocab_size=64000, rope_theta=5e6,
                      norm_eps=1e-5)
    api = build_model(cfg)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    slots, m, bs = 64, 128, 16

    def placed(shapes, specs):
        sh = shardings_from_specs(mesh, specs, shapes)
        return jax.tree_util.tree_map(
            lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
            shapes, sh)

    params = placed(*abstract(api.init, jax.ShapeDtypeStruct((2,), jnp.uint32)))
    pool = placed(*abstract(lambda: api.paged_cache_init(slots * m + 1, bs)))
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def step(p, cur, pos, tables, cache):
        logits, cache = api.decode_step_paged(p, cur, pos, tables, cache, bs)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)  # noqa: E731
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=(4,)).lower(
            params, ints(slots, 1), ints(slots), ints(slots, m), pool).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30
    hlo = compiled.as_text()
    gathered = re.findall(r"= (\w+)\[([\d,]*)\][^ ]* all-gather(?:-start)?\(", hlo)
    assert all(int(np.prod([int(d) for d in dims.split(",")])) <= 4 * slots
               for _, dims in gathered), gathered
    reduced = re.findall(r"= \w+\[([\d,]*)\][^ ]* all-reduce(?:-start)?\(", hlo)
    assert f"{slots},1,4096" in reduced, reduced
