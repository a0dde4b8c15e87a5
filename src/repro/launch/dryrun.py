import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
    " --xla_force_host_platform_device_count=512"
).strip()
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell: build the production mesh (16×16 single-pod, 2×16×16
multi-pod) over 512 placeholder host devices, assemble NamedShardings from
the models' logical param specs, then

    with jax.set_mesh(mesh):
        lowered  = jax.jit(step, in_shardings=…, out_shardings=…,
                           donate_argnums=…).lower(*ShapeDtypeStructs)
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline

No arrays are allocated — state, caches and inputs are ``jax.eval_shape``
/ ``ShapeDtypeStruct`` stand-ins.

Roofline terms: XLA's cost_analysis counts a while-loop body ONCE
regardless of trip count, so the scanned full-depth compile under-reports
FLOPs by ~n_layers.  We therefore also compile 1-block and 2-block
UNROLLED probe variants of the same cell and extrapolate exactly
(uniform stacks ⇒ cost(L) = base + L·Δ).  The full-depth compile remains
the shardability + memory_analysis proof.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all [--both-meshes] --out results/dryrun.jsonl
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import flags
from repro.configs import (
    ARCH_NAMES,
    SHAPES,
    cell_is_runnable,
    get_config,
    shape_by_name,
)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (
    HW,
    RooflineTerms,
    model_flops,
    roofline_from_compiled,
)
from repro.launch.sharding import abstract, batch_shardings, shardings_from_specs
from repro.models.registry import build_model
from repro.train.loop import make_train_step
from repro.train.optimizer import AdamWConfig


def abstract_init(api):
    """(param ShapeDtypeStructs, logical specs) with zero allocation."""
    return abstract(api.init, jax.ShapeDtypeStruct((2,), jnp.uint32))


def abstract_cache(api, batch: int, max_len: int):
    return abstract(lambda: api.cache_init(batch, max_len))


def param_stats(params_struct, specs) -> dict:
    total = 0
    expert = 0

    def walk(p, s):
        nonlocal total, expert
        total += p.size
        if isinstance(s, tuple) and "experts" in s:
            expert += p.size

    jax.tree_util.tree_map(
        walk, params_struct, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    return {"total": int(total), "expert": int(expert)}


def _scan_unit(cfg) -> int:
    """Layers per scan step (the linearity unit for probe extrapolation)."""
    if cfg.local_block:
        return cfg.local_block
    if cfg.hybrid_block:
        return cfg.hybrid_block
    return 1


def _probe_cfg(cfg, units: int):
    per = _scan_unit(cfg)
    changes = {"n_layers": per * units}
    if cfg.n_enc_layers:
        changes["n_enc_layers"] = max(
            1, cfg.n_enc_layers * (per * units) // cfg.n_layers
        )
    return dataclasses.replace(cfg, **changes)


def build_lowered(cfg, shape, mesh):
    """Assemble shardings and lower the cell's step function.

    REPRO_SERVE_LAYOUT=1 switches prefill/decode cells to the SERVING
    param layout (§Perf iteration 3): bf16 weights, dense weights
    replicated over the data axis (no per-step FSDP all-gather; MoE expert
    banks keep their data shard — too large to replicate).  Default is the
    training layout: right whenever weights+cache approach HBM (see
    EXPERIMENTS.md §Perf for the measured trade).
    """

    api = build_model(cfg)
    params_struct, param_specs = abstract_init(api)
    serve_layout = (
        shape.kind in ("prefill", "decode")
        and os.environ.get("REPRO_SERVE_LAYOUT", "0") == "1"
    )
    if serve_layout:
        # bf16 serving weights (float leaves only)
        params_struct = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape,
                jnp.bfloat16 if x.dtype == jnp.float32 else x.dtype,
            ),
            params_struct,
        )
        if cfg.n_experts:
            # keep expert banks data-sharded; replicate only dense weights
            def _serve_spec(spec):
                if isinstance(spec, tuple) and "experts" in spec:
                    return spec
                return tuple(None if s == "embed" else s for s in spec) \
                    if isinstance(spec, tuple) else spec
            param_specs = jax.tree_util.tree_map(
                _serve_spec, param_specs,
                is_leaf=lambda x: isinstance(x, tuple),
            )
        else:
            param_specs = jax.tree_util.tree_map(
                lambda sp: tuple(None if s == "embed" else s for s in sp)
                if isinstance(sp, tuple) else sp,
                param_specs,
                is_leaf=lambda x: isinstance(x, tuple),
            )
    param_sh = shardings_from_specs(mesh, param_specs, params_struct)
    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        grad_accum = int(os.environ.get("REPRO_DRYRUN_GRAD_ACCUM", "1"))
        m_dtype = os.environ.get("REPRO_DRYRUN_M_DTYPE", "float32")
        opt_cfg = AdamWConfig(m_dtype=m_dtype)
        step = make_train_step(api, opt_cfg, grad_accum=grad_accum)
        state_struct = jax.eval_shape(
            lambda p: {
                "params": p,
                "opt": {
                    "m": jax.tree_util.tree_map(
                        lambda x: jnp.zeros(
                            x.shape,
                            jnp.bfloat16 if m_dtype == "bfloat16"
                            else jnp.float32,
                        ),
                        p,
                    ),
                    "v": jax.tree_util.tree_map(
                        lambda x: jnp.zeros(x.shape, jnp.float32), p
                    ),
                    "count": jnp.zeros((), jnp.int32),
                },
                "step": jnp.zeros((), jnp.int32),
            },
            params_struct,
        )
        state_sh = {
            "params": param_sh,
            "opt": {"m": param_sh, "v": param_sh, "count": repl},
            "step": repl,
        }
        in_specs = api.input_specs(shape)
        batch_sh = batch_shardings(mesh, in_specs)
        jitted = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, repl),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(state_struct, in_specs)
    elif shape.kind == "prefill":
        in_specs = api.input_specs(shape)
        batch_sh = batch_shardings(mesh, in_specs)

        def prefill_fn(params, batch):
            return api.prefill(params, batch, max_len=shape.seq_len)

        jitted = jax.jit(prefill_fn, in_shardings=(param_sh, batch_sh))
        lowered = jitted.lower(params_struct, in_specs)
    else:  # decode
        cache_struct, cache_spec = abstract_cache(
            api, shape.global_batch, shape.seq_len
        )
        cache_sh = shardings_from_specs(mesh, cache_spec, cache_struct)
        in_specs = api.input_specs(shape)
        batch_sh = batch_shardings(mesh, in_specs)

        def serve_step(params, token, pos, cache):
            return api.decode_step(params, token, pos, cache)

        jitted = jax.jit(
            serve_step,
            in_shardings=(param_sh, batch_sh["token"], batch_sh["pos"], cache_sh),
            out_shardings=(None, cache_sh),
            donate_argnums=(3,),
        )
        lowered = jitted.lower(
            params_struct, in_specs["token"], in_specs["pos"], cache_struct
        )
    pstats = param_stats(params_struct, param_specs)
    return lowered, pstats


def probe_roofline(cfg, shape, mesh) -> dict:
    """1-block / 2-block unrolled probe compiles → exact extrapolated terms."""
    per = _scan_unit(cfg)
    n_units = cfg.n_layers // per
    with flags.unroll_scans():
        t1, _ = _compile_terms(_probe_cfg(cfg, 1), shape, mesh)
        if n_units > 1:
            t2, _ = _compile_terms(_probe_cfg(cfg, 2), shape, mesh)
        else:
            t2 = t1
    def extrap(a, b):
        return a + (n_units - 1) * (b - a)

    coll_bd = {
        k: int(extrap(t1.collective_breakdown.get(k, 0),
                      t2.collective_breakdown.get(k, 0)))
        for k in set(t1.collective_breakdown) | set(t2.collective_breakdown)
    }
    return RooflineTerms(
        flops_per_device=extrap(t1.flops_per_device, t2.flops_per_device),
        bytes_per_device=extrap(t1.bytes_per_device, t2.bytes_per_device),
        collective_bytes=extrap(t1.collective_bytes, t2.collective_bytes),
        collective_breakdown=coll_bd,
        peak_memory_bytes=0.0,
    )


def _compile_terms(cfg, shape, mesh):
    lowered, pstats = build_lowered(cfg, shape, mesh)
    compiled = lowered.compile()
    return roofline_from_compiled(compiled), pstats


def lower_cell(arch: str, shape_name: str, multi_pod: bool, verbose: bool = True,
               skip_probes: bool = False):
    cfg = get_config(arch)
    shape = shape_by_name(shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not cell_is_runnable(cfg, shape):
        rec.update(status="skipped", reason=cfg.notes)
        print(f"[{mesh_name}] {arch} × {shape_name}: SKIPPED ({cfg.notes})")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        lowered, pstats = build_lowered(cfg, shape, mesh)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
        try:
            mem_str = str(compiled.memory_analysis())
        except Exception as e:  # pragma: no cover
            mem_str = f"<unavailable: {e}>"
        scanned_terms = roofline_from_compiled(compiled)

        if skip_probes:
            terms = scanned_terms
        else:
            terms = probe_roofline(cfg, shape, mesh)
            terms.peak_memory_bytes = scanned_terms.peak_memory_bytes

    n_active = pstats["total"] - pstats["expert"] + (
        pstats["expert"] * cfg.experts_per_token // max(cfg.n_experts, 1)
    )
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mf = model_flops(n_active, tokens, "train")
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mf = model_flops(n_active, tokens, "inference")
    else:
        tokens = shape.global_batch
        mf = model_flops(n_active, tokens, "inference")

    n_chips = 512 if multi_pod else 256
    rec.update(
        status="ok",
        n_chips=n_chips,
        params_total=pstats["total"],
        params_active=int(n_active),
        tokens_per_step=int(tokens),
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        total_s=round(time.perf_counter() - t0, 2),
        memory_analysis=mem_str,
        model_flops_global=mf,
        model_flops_per_device=mf / n_chips,
        roofline=terms.as_dict(),
        roofline_scanned_raw=scanned_terms.as_dict(),
    )
    rec["useful_flops_ratio"] = (
        (mf / n_chips) / terms.flops_per_device if terms.flops_per_device else None
    )
    if verbose:
        r = terms
        print(
            f"[{mesh_name}] {arch} × {shape_name}: OK "
            f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s, "
            f"total {rec['total_s']:.1f}s)\n"
            f"  params={pstats['total']/1e9:.2f}B active={n_active/1e9:.2f}B "
            f"tokens/step={tokens} useful_ratio="
            f"{rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)}\n"
            f"  per-device: flops={r.flops_per_device:.3e} "
            f"bytes={r.bytes_per_device:.3e} coll={r.collective_bytes:.3e}\n"
            f"  terms(s): compute={r.t_compute:.4f} memory={r.t_memory:.4f} "
            f"collective={r.t_collective:.4f} → bottleneck={r.bottleneck}\n"
            f"  memory_analysis: {mem_str[:260]}",
            flush=True,
        )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=[s.name for s in SHAPES])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="every runnable cell")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-probes", action="store_true",
                    help="skip unrolled probe compiles (raw scanned costs only)")
    ap.add_argument("--out", type=str, default=None, help="JSONL output path")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_NAMES:
            for s in SHAPES:
                cells.append((a, s.name))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    out_f = None
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        out_f = open(args.out, "a")
    failures = 0
    for mp in meshes:
        for arch, shp in cells:
            try:
                rec = lower_cell(arch, shp, mp, skip_probes=args.skip_probes)
            except Exception:
                rec = {
                    "arch": arch, "shape": shp,
                    "mesh": "2x16x16" if mp else "16x16",
                    "status": "error", "trace": traceback.format_exc(),
                }
                failures += 1
                print(f"[{'2x16x16' if mp else '16x16'}] {arch} × {shp}: FAILED")
                print(rec["trace"].splitlines()[-1], flush=True)
            if out_f:
                out_f.write(json.dumps(rec) + "\n")
                out_f.flush()
    if out_f:
        out_f.close()
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
