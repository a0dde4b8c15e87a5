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


# ---------------------------------------------------------------------------
# paged decode: the kernel, and the decode step that calls it
# ---------------------------------------------------------------------------

# yi-6b: 64 slots of 128 blocks of 16 positions; one chip holds 4 KV heads
# of 8 layers, a chip of the 1x4 mesh 1 KV head of 32
@pytest.mark.parametrize("hkv,layers,dh", [(4, 8, 128), (1, 32, 128),
                                           (4, 2, 256)])
def test_paged_decode_compiles(one_chip, hkv, layers, dh):
    from repro.kernels.paged_decode.kernel import paged_decode_pallas

    slots, m, bs = 64, 128, 16
    pool = ((layers, hkv, (slots * m + 1) * bs, dh), jnp.bfloat16)
    _compile_for_chip(
        functools.partial(paged_decode_pallas, block_size=bs),
        ((slots, 8 * hkv, dh), jnp.bfloat16), pool, pool,
        ((slots, m), jnp.int32), ((slots,), jnp.int32), ((), jnp.int32),
        sharding=one_chip,
    )


def _yi6b(n_layers):
    from repro.configs.base import ModelConfig

    return ModelConfig(name="yi-6b-full", family="dense", n_layers=n_layers,
                       d_model=4096, n_heads=32, n_kv_heads=4, head_dim=128,
                       d_ff=11008, vocab_size=64000, rope_theta=5e6,
                       norm_eps=1e-5)


def _compiled_paged_step(topo, n_layers, n_chips, slots=64, m=128, bs=16):
    """The continuous engine's greedy paged decode step for described
    chips (a 1 x n_chips mesh), donated pool and all → compiled text."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.launch.sharding import abstract, shardings_from_specs
    from repro.models.registry import build_model

    api = build_model(_yi6b(n_layers))
    mesh = Mesh(np.array(topo.devices[:n_chips]).reshape(1, n_chips),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)

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
        return jax.jit(step, donate_argnums=(4,)).lower(
            params, ints(slots, 1), ints(slots), ints(slots, m), pool
        ).compile().as_text()


_RESTACKS = ("copy", "dynamic-slice", "dynamic-update-slice", "gather",
             "transpose")


def _pool_sized_restacks(hlo, elems):
    """Ops that copy, slice, restack or gather an array of at least
    ``elems`` elements (one layer's pool: what the paged step must never
    move), by op kind or by the fusion's name."""
    import re

    import numpy as np

    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(", line)
        if not m:
            continue
        name, dims, op = m.groups()
        n = int(np.prod([int(d) for d in dims.split(",") if d]))
        kind = op if op != "fusion" else name
        if n >= elems and any(w in kind for w in _RESTACKS):
            out.append(line.strip()[:160])
    return out


def _kernel_calls(hlo):
    """(computation, line) of every Mosaic call in the compiled text."""
    comp, out = None, []
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = line.split(" ", 1)[0].lstrip("%")
        if 'custom_call_target="tpu_custom_call"' in line:
            out.append((comp, line.strip()))
    return out


@pytest.mark.parametrize("n_layers,n_chips", [(8, 1), (32, 4)])
def test_decode_step_attends_the_pool_in_place(topo, monkeypatch, n_layers,
                                                n_chips):
    """Yi-6B's paged decode step as it runs on a TPU (one chip at 8
    layers; the 1x4 mesh at 32): the pool rides the layer scan with no
    pool-sized slice, copy, restack or gather; the paged decode kernel is
    the step's one Mosaic call, inside the layer loop (once a layer); on
    four chips the only all-gather is the argmax's values; and the
    kernel's operands are not flash attention's, so ``prefill_mfu`` does
    not take the decode step for a prefill."""
    import re
    import sys
    from pathlib import Path

    import numpy as np

    import repro.models.common as common

    monkeypatch.setattr(common, "on_tpu", lambda: True)
    slots, m, bs = 64, 128, 16
    hlo = _compiled_paged_step(topo, n_layers, n_chips, slots, m, bs)
    view = slots * m * bs * (4 // n_chips) * 128   # one layer's whole view
    assert _pool_sized_restacks(hlo, view) == []
    calls = _kernel_calls(hlo)
    assert len(calls) == 1, calls
    body, line = calls[0]
    assert f"body=%{body}," in hlo, "the kernel is not inside the layer loop"
    assert line.split(" = ", 1)[0].lstrip("%").startswith("paged_decode")
    gathered = re.findall(r"= \w+\[([\d,]*)\][^ ]* all-gather(?:-start)?\(", hlo)
    assert all(int(np.prod([int(d) for d in dims.split(",")])) <= n_chips * slots
               for dims in gathered), gathered
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.costs.flash_attention import is_flash
    from bench.tracing import Kernel, parse_shapes

    # the compiled text names operands without shapes; their layout
    # constraints give them in order
    head = line.split(" custom-call(")[0].partition(" = ")[2]
    typed = line.split("operand_layout_constraints=")[1].split("}}, ")[0]
    kernel = Kernel("paged_decode", 1.0, parse_shapes(head), parse_shapes(typed))
    assert len(kernel.operands) == 6 and not is_flash(kernel), kernel
