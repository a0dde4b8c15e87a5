"""Serving example: batched requests through prefill + decode.

Loads (or quickly trains) a small LM on the indexed corpus, then serves a
batch of molecular-id prompts through the Engine — prefill once, decode
with per-sequence positions, EOS stopping.

    PYTHONPATH=src python examples/serve_lm.py
"""

import dataclasses
import tempfile
from pathlib import Path

import jax

from repro.configs import get_config
from repro.core import RecordStore, build_index
from repro.core.sdfgen import CorpusSpec, generate_corpus
from repro.data.pipeline import IndexedDataset
from repro.serve.engine import Engine, ServeConfig
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    cfg = dataclasses.replace(
        get_config("yi-6b"),
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=256, vocab_size=512,
    )
    root = Path(tempfile.mkdtemp()) / "c"
    spec = CorpusSpec(n_files=2, records_per_file=1_000)
    generate_corpus(root, spec)
    store = RecordStore(root)
    ds = IndexedDataset(store, build_index(store), seq_len=96)

    print("fitting a small LM on the indexed corpus (30 steps)…")
    tr = Trainer(
        cfg,
        TrainerConfig(seq_len=96, global_batch=8, steps=30, ckpt_every=30,
                      opt=AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=30)),
        ds,
        Path(tempfile.mkdtemp()),
    )
    _, state, hist = tr.run()
    print(f"  loss {hist[0]['loss']:.3f} → {hist[-1]['loss']:.3f}")

    engine = Engine(cfg, state["params"],
                    ServeConfig(max_new_tokens=24, max_len=160))
    prompts = [
        "InChI=1S/C12H22O2/",
        "InChI=1S/C8H9NO2/",
        "InChI=1S/C12H22O2/",   # duplicate: batched decode must agree
    ]
    print(f"serving batch of {len(prompts)} requests…")
    results = engine.generate(prompts)
    for i, r in enumerate(results):
        print(f"  [{i}] prompt_len={r.prompt_len} steps={r.steps} "
              f"prefill={r.prefill_s*1e3:.0f}ms "
              f"decode={r.tokens_per_s:.0f} tok/s")
        print(f"      → {r.text[:60]!r}")
    # batched decode determinism: identical prompts, identical continuations
    assert results[0].token_ids == results[2].token_ids, \
        "identical prompts diverged in one batch!"
    print("batched decode determinism verified")


if __name__ == "__main__":
    main()
