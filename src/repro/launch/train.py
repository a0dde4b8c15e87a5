"""Training launcher: ``python -m repro.launch.train --arch <id> …``.

Wires the full stack for a real run: corpus/index data plane → model from
the assigned-architecture registry → sharded train step on the requested
mesh → catalog checkpoints + heartbeats.  On the CPU container the mesh is
(1,1) and the reduced smoke config is the default; on a TPU host, pass
``--full-config`` and ``--mesh DATAxMODEL``.
"""

from __future__ import annotations

import argparse
import dataclasses
from pathlib import Path

import jax

from repro.configs import ARCH_NAMES, get_config
from repro.core import RecordStore, build_index
from repro.core.sdfgen import CorpusSpec, generate_corpus
from repro.device import use_compile_cache
from repro.data.pipeline import IndexedDataset
from repro.launch.mesh import mesh_from_str
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="yi-6b")
    ap.add_argument("--full-config", action="store_true",
                    help="use the full published config (pod hardware)")
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 16x16")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--compressor", default="int8_ef",
                    choices=["int8_ef", "int8_pc_ef", "topk_ef"],
                    help="gradient compression scheme (with --compress-grads)")
    ap.add_argument("--topk-frac", type=float, default=0.1,
                    help="kept fraction for --compressor topk_ef")
    ap.add_argument("--workdir", default="runs/train")
    ap.add_argument("--corpus-records", type=int, default=4000)
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = cfg.smoke()
    mesh = mesh_from_str(args.mesh)

    root = Path(args.workdir) / "corpus"
    spec = CorpusSpec(n_files=4, records_per_file=args.corpus_records // 4)
    generate_corpus(root, spec)
    store = RecordStore(root)
    ds = IndexedDataset(store, build_index(store, workers=2), args.seq_len)

    tcfg = TrainerConfig(
        seq_len=args.seq_len,
        global_batch=args.global_batch,
        steps=args.steps,
        ckpt_every=args.ckpt_every,
        grad_accum=args.grad_accum,
        compress_grads=args.compress_grads,
        compressor=args.compressor,
        topk_frac=args.topk_frac,
        opt=AdamWConfig(warmup_steps=max(2, args.steps // 10),
                        total_steps=args.steps),
    )
    tr = Trainer(cfg, tcfg, ds, Path(args.workdir), mesh=mesh)

    def log(step, rec):
        if step % 5 == 0:
            print(f"step {step:5d} loss {rec['loss']:.4f} "
                  f"gnorm {rec['grad_norm']:.2f} {rec['dt']*1e3:.0f} ms",
                  flush=True)

    # Trainer.run enters the mesh context itself (sharding rules active
    # while the step function traces).
    final, _, hist = tr.run(on_step=log)
    if hist:
        print(f"done: {final} steps, loss {hist[0]['loss']:.4f} → "
              f"{hist[-1]['loss']:.4f}, checkpoints at "
              f"{tr.ckpt.root} (latest {tr.ckpt.latest_step()})")
    else:  # resumed at or past --steps: nothing left to train
        print(f"done: already at step {final} (restored checkpoint), "
              f"checkpoints at {tr.ckpt.root} (latest {tr.ckpt.latest_step()})")


if __name__ == "__main__":
    main()
