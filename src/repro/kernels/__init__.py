"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel ships as ``kernel.py`` (pl.pallas_call + BlockSpec tiling),
``ops.py`` (jit'd dispatching wrapper) and ``ref.py`` (pure-jnp oracle).
On a TPU every entry point runs its Mosaic kernel (``repro.device.on_tpu``
decides, once, for all of them).  On a CPU the kernels run only under
``interpret=True`` — in tests — and the entry points take the reference
implementations; ``tests/test_chip_compile.py`` compiles each kernel for
a described v5e chip.

* ``hash_mix``        — 128-bit mixing digest of packed identifiers
                        (the InChIKey role for on-device analytics).
* ``sorted_probe``    — fence-partitioned membership probe against a sorted
                        digest table (the paper's index lookup, TPU-native).
* ``tanimoto``        — batched Tanimoto top-k over packed fingerprint
                        bit-planes (the similarity query modality).
* ``flash_attention`` — causal/sliding-window GQA flash attention.
* ``paged_decode``    — one-token GQA attention over a paged KV pool,
                        reading each slot's live blocks where they lie.
* ``ssd_scan``        — Mamba2 SSD inter-chunk state recurrence.
"""

from .hash_mix.ops import hash_mix, hash_mix_u64
from .sorted_probe.ops import sorted_probe
from .tanimoto.ops import tanimoto_topk
from .flash_attention.ops import flash_attention
from .paged_decode.ops import paged_decode
from .ssd_scan.ops import ssd_scan
