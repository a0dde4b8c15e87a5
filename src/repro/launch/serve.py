"""Serving launcher: ``python -m repro.launch.serve --arch <id> …``.

Builds the engine for the requested architecture (reduced config on CPU)
and serves a batch of prompts, reporting prefill/decode timings.

``--mesh DATAxMODEL`` serves sharded: params go to their logical-rule
shardings (:mod:`repro.dist.logical`), the request batch spreads over the
data axis, and batched decode runs under the mesh so every ``constrain``
in the model takes effect.  The default ("1x1") stays single-device.

``--continuous`` serves through the paged-KV continuous-batching engine
instead (:mod:`repro.serve.scheduler`): prompts are submitted as
independent requests that admit into ``--max-slots`` decode lanes backed
by ``--block-size`` KV blocks, and the report adds the TTFT/inter-token
SLO percentiles plus the prefix-cache hit counters.  Prompts sharing a
block-aligned prefix share its KV via the prefix cache (on by default;
``--no-prefix-cache`` disables sharing — outputs are byte-identical
either way).  With ``--mesh`` it serves sharded as the static engine
does: weights at their logical shardings, the KV pool split by KV head.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import ARCH_NAMES, get_config
from repro.device import use_compile_cache
from repro.launch.mesh import mesh_from_str
from repro.models.registry import build_model
from repro.serve.engine import Engine, ServeConfig
from repro.serve.kvcache import PagedCacheSpec, blocks_for
from repro.serve.scheduler import ContinuousEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="yi-6b")
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 2x4")
    ap.add_argument("--continuous", action="store_true",
                    help="serve via the paged-KV continuous-batching engine")
    ap.add_argument("--max-slots", type=int, default=8,
                    help="decode batch width of the continuous engine")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV rows per paged-cache block")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="share block-aligned prompt prefixes across "
                         "requests (continuous mode; byte-identical output "
                         "either way)")
    ap.add_argument("--prompts", nargs="*", default=[
        "InChI=1S/C12H22O2/", "InChI=1S/C8H9NO2/",
    ])
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = cfg.smoke()
    if cfg.family == "vlm":
        print("note: vlm frontend stubbed — serving text-only prompts")
    api = build_model(cfg)
    params, specs = api.init(jax.random.PRNGKey(0))

    mesh = mesh_from_str(args.mesh)
    if args.continuous:
        if not api.supports_paged:
            raise SystemExit(
                f"--arch {args.arch} has no paged-KV decode path "
                "(windowed attention or non-transformer family); "
                "drop --continuous")
        m = blocks_for(args.max_len, args.block_size)
        # headroom past full slot occupancy keeps prefix-index entries
        # resident between requests instead of thrashing under pressure
        headroom = m if args.prefix_cache else 0
        spec = PagedCacheSpec(
            n_blocks=args.max_slots * m + headroom + 2,  # + trash
            block_size=args.block_size,
            max_slots=args.max_slots,
            max_blocks_per_seq=m,
        )
        eng = ContinuousEngine(
            cfg, params, spec,
            ServeConfig(max_new_tokens=args.max_new_tokens,
                        max_len=spec.max_len),
            prefix_cache=args.prefix_cache,
            mesh=mesh, param_specs=specs,
        )
        print(f"serving {len(args.prompts)} prompts on {args.arch} "
              f"({'full' if args.full_config else 'smoke'} config, "
              f"continuous: {args.max_slots} slots x "
              f"{spec.max_blocks_per_seq} blocks of {args.block_size}, "
              f"mesh {args.mesh})…")
        for i, r in enumerate(eng.generate(args.prompts)):
            print(f"[{i}] prefill {r.prefill_s*1e3:.0f} ms, "
                  f"{r.tokens_per_s:.1f} tok/s → {r.text[:60]!r}")
        slo = eng.slo_ms()
        c = eng.counters()
        print(f"slo: ttft p50 {slo['ttft_p50_ms']:.1f} ms / "
              f"p99 {slo['ttft_p99_ms']:.1f} ms, itl p50 "
              f"{slo['itl_p50_ms']:.2f} ms / p99 {slo['itl_p99_ms']:.2f} ms, "
              f"mean admission wait "
              f"{c['queue_wait_s'] / max(c['prefills'], 1) * 1e3:.1f} ms")
        if "pfx_entries" in c:
            print(f"prefix cache: hit rate {c['prefix_hit_rate']:.2f} "
                  f"({c['prefix_hits']:.0f}/"
                  f"{c['prefix_hits'] + c['prefix_misses']:.0f}), "
                  f"{c['prefill_tokens_saved']:.0f} prefill tokens saved, "
                  f"{c['pfx_entries']:.0f} entries resident")
        else:
            print("prefix cache: off")
        eng.close()
        return

    eng = Engine(
        cfg, params,
        ServeConfig(max_new_tokens=args.max_new_tokens, max_len=args.max_len),
        mesh=mesh, param_specs=specs,
    )
    print(f"serving {len(args.prompts)} prompts on {args.arch} "
          f"({'full' if args.full_config else 'smoke'} config, "
          f"mesh {args.mesh})…")
    for i, r in enumerate(eng.generate(args.prompts)):
        print(f"[{i}] prefill {r.prefill_s*1e3:.0f} ms, "
              f"{r.tokens_per_s:.1f} tok/s → {r.text[:60]!r}")


if __name__ == "__main__":
    main()
