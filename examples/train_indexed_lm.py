"""End-to-end training driver: byte-offset-indexed corpus → LM training.

The data plane is the paper's architecture verbatim: records are fetched
per step through the index with grouped, offset-sorted seeks; addressing
is stateless so the checkpoint stores one integer of pipeline state.
Training runs with catalog checkpoints and demonstrates restart.

Defaults are sized for the 1-core CPU container (a ~3M-param model, 80
steps).  ``--preset 100m --steps 300`` is the full-size configuration for
real hardware.

    PYTHONPATH=src python examples/train_indexed_lm.py [--steps 80]
"""

import argparse
import dataclasses
import tempfile
from pathlib import Path

from repro.configs import get_config
from repro.core import RecordStore, build_index
from repro.core.sdfgen import CorpusSpec, generate_corpus
from repro.data.pipeline import IndexedDataset
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig

PRESETS = {
    # name: (layers, d_model, heads, kv, d_ff, seq, batch) — vocab fixed 512
    "tiny": (2, 128, 4, 2, 256, 128, 8),
    "20m": (6, 384, 6, 2, 1024, 256, 8),
    "100m": (12, 768, 12, 4, 2048, 512, 16),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--preset", choices=PRESETS, default="tiny")
    ap.add_argument("--records", type=int, default=8_000)
    ap.add_argument("--workdir", type=str, default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()

    L, D, H, KV, F, S, B = PRESETS[args.preset]
    cfg = dataclasses.replace(
        get_config("yi-6b"),
        n_layers=L, d_model=D, n_heads=H, n_kv_heads=KV,
        head_dim=D // H, d_ff=F, vocab_size=512,
    )

    root = Path(tempfile.mkdtemp()) / "corpus" if not args.workdir else (
        Path(args.workdir) / "corpus"
    )
    spec = CorpusSpec(n_files=4, records_per_file=args.records // 4)
    generate_corpus(root, spec)
    store = RecordStore(root)
    idx = build_index(store)
    ds = IndexedDataset(store, idx, seq_len=S)
    print(f"indexed dataset: {len(ds)} records "
          f"({ds.stats.verify_failures} verify failures)")

    workdir = Path(args.workdir) if args.workdir else Path(tempfile.mkdtemp())
    tcfg = TrainerConfig(
        seq_len=S, global_batch=B, steps=args.steps, ckpt_every=20,
        compress_grads=args.compress_grads,
        opt=AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps),
    )
    tr = Trainer(cfg, tcfg, ds, workdir)
    n_params = None

    def log(step, rec):
        if step % 10 == 0 or step == args.steps - 1:
            print(f"  step {step:4d}  loss {rec['loss']:.4f}  "
                  f"gnorm {rec['grad_norm']:.2f}  lr {rec['lr']:.2e}  "
                  f"{rec['dt']*1e3:.0f} ms")

    print(f"training {args.preset} preset for {args.steps} steps "
          f"(ckpt every {tcfg.ckpt_every} into {workdir})")
    final, state, hist = tr.run(on_step=log)
    first, last = hist[0]["loss"], hist[-1]["loss"]
    print(f"\nloss {first:.4f} → {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'}); "
          f"latest checkpoint step {tr.ckpt.latest_step()}")
    assert last < first, "training failed to reduce loss"
    # fetch-pattern stats: the paper's access optimization at work
    print(f"data plane: {ds.stats.fetches} record fetches, "
          f"{ds.stats.retries} straggler retries, "
          f"{ds.stats.verify_failures} verification failures")


if __name__ == "__main__":
    main()
