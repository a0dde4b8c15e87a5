"""Full-InChI-style keys made in bulk from a seed.

The program's own corpus generator (``repro.core.sdfgen``) builds each
identifier through ``canonical_id(molecule_from_cid(cid))`` at about 6k
keys/s, far too slow for a PubChem-shaped key set inside a run's set-up.
This generator keeps the same layered layout and the same construction —
a 15-atom backbone that spells out the key's number in base 4 (so every
key is distinct), 4-28 decoration atoms, mostly carbon, each bonded to an
earlier atom, up to two ring closures, bond orders, stereo tags, Hill
formula, ``/e`` element string, ``/c`` connection table, ``/h`` hydrogen
counts and an optional ``/t`` stereo layer — with every random draw made
as one numpy array over all keys.  Its length distribution matches
``canonical_id`` (mean about 293 bytes, 5th-95th percentile about
186-402; see ``tests/bench/test_bench_harness.py``).
"""

from __future__ import annotations

import hashlib
from typing import List, Sequence

import numpy as np

__all__ = ["make_keys", "digests"]

_ELEMENTS = ("C", "N", "O", "S", "P", "F", "Cl", "Br")
_VALENCE = np.array([4, 3, 2, 2, 3, 1, 1, 1], np.int16)
_BACKBONE = 15              # base-4 digits: 4**15 > PubChem's 1.77e8 ids
_MAX_EXTRA = 28
_MAX_ATOMS = _BACKBONE + _MAX_EXTRA
_MAX_RINGS = 2
_SHIFT = 64                 # atom index < 64 packs a bond into one int


def _bond_tokens() -> np.ndarray:
    """Text of every packed bond ``((a * 64 + b) * 2 + double) * 2 + stereo``."""
    out = np.empty(_SHIFT * _SHIFT * 4, dtype=object)
    for a in range(_SHIFT):
        for b in range(_SHIFT):
            for dbl in (0, 1):
                for st in (0, 1):
                    code = ((a * _SHIFT + b) * 2 + dbl) * 2 + st
                    out[code] = f"{a + 1}-{b + 1}" + ("*2" if dbl else "")
    return out


_TOKENS = _bond_tokens()
_WIDTH = _BACKBONE - 1 + _MAX_EXTRA + _MAX_RINGS
# each table ends with "" for an empty cell
_CONN = np.array(list(_TOKENS) + ["," + t for t in _TOKENS] + [""], dtype=object)
_TAGS = np.array([f"/t{p + 1}" for p in range(_WIDTH)]
                 + [f",{p + 1}" for p in range(_WIDTH)] + [""], dtype=object)
_HS = np.array([str(d) for d in range(8)] + [f",{d}" for d in range(8)] + [""],
               dtype=object)
_SYMBOLS = np.array(_ELEMENTS + ("",), dtype=object)
_COUNTS = [np.array([""] + [f"{e}{c}" for c in range(1, _MAX_ATOMS + 1)],
                    dtype=object) for e in _ELEMENTS]
_H_COUNTS = np.array([""] + [f"H{c}" for c in range(1, 4 * _MAX_ATOMS + 1)],
                     dtype=object)
_ALPHA = sorted(range(1, len(_ELEMENTS)), key=lambda e: _ELEMENTS[e])
_SENTINEL = np.int64(1 << 40)
_CHUNK = 1 << 18            # keys made at once: bounds the host memory used


def make_keys(ids: Sequence[int], seed: int) -> List[str]:
    """One key per entry of ``ids`` (distinct ids give distinct keys)."""
    ids = np.asarray(ids, dtype=np.int64)
    out: List[str] = []
    for c, lo in enumerate(range(0, len(ids), _CHUNK)):
        out += _make_chunk(ids[lo: lo + _CHUNK], np.random.default_rng([seed, c]))
    return out


def _make_chunk(ids: np.ndarray, rng: np.random.Generator) -> List[str]:
    n = len(ids)
    # backbone: base-4 digits of the id, elements C N O S
    digits = (ids[:, None] // (4 ** np.arange(_BACKBONE, dtype=np.int64))) % 4
    n_extra = rng.integers(4, _MAX_EXTRA + 1, n)
    n_atoms = _BACKBONE + n_extra
    draw = rng.integers(0, 256, (n, _MAX_EXTRA))
    other = 1 + rng.integers(0, len(_ELEMENTS) - 1, (n, _MAX_EXTRA))
    extra = np.where(draw < 160, 0, other)
    elem = np.concatenate([digits, extra], axis=1)                 # (n, 43)
    live = np.arange(_MAX_ATOMS)[None, :] < n_atoms[:, None]
    elem = np.where(live, elem, -1)

    # bonds: backbone chain, then each decoration atom to an earlier atom
    i = np.arange(_BACKBONE, _MAX_ATOMS)[None, :]
    parent = (rng.random((n, _MAX_EXTRA)) * i).astype(np.int64)
    double = rng.random((n, _MAX_EXTRA)) < 0.2
    stereo = ~double & (rng.random((n, _MAX_EXTRA)) < 0.125)
    ext_live = live[:, _BACKBONE:]
    chain_a = np.broadcast_to(np.arange(_BACKBONE - 1), (n, _BACKBONE - 1))
    ring_a = (rng.random((n, _MAX_RINGS)) * n_atoms[:, None]).astype(np.int64)
    ring_b = (rng.random((n, _MAX_RINGS)) * n_atoms[:, None]).astype(np.int64)
    ring_live = (np.arange(_MAX_RINGS)[None, :]
                 < rng.integers(0, _MAX_RINGS + 1, n)[:, None])
    ring_live &= ring_a != ring_b
    lo, hi = np.minimum(ring_a, ring_b), np.maximum(ring_a, ring_b)

    a = np.concatenate([chain_a, parent, lo], axis=1)
    b = np.concatenate([chain_a + 1, np.broadcast_to(i, parent.shape), hi], axis=1)
    dbl = np.concatenate([np.zeros_like(chain_a, bool), double,
                          np.zeros_like(lo, bool)], axis=1)
    st = np.concatenate([np.zeros_like(chain_a, bool), stereo,
                         np.zeros_like(lo, bool)], axis=1)
    ok = np.concatenate([np.ones_like(chain_a, bool), ext_live, ring_live], axis=1)
    code = np.where(ok, ((a * _SHIFT + b) * 2 + dbl) * 2 + st, _SENTINEL)
    code = np.sort(code, axis=1)
    n_bonds = ok.sum(axis=1)

    # hydrogens: valence left after the bonds, floored at zero
    order = np.where(ok, 1 + dbl, 0).astype(np.int16)
    used = np.zeros((n, _MAX_ATOMS), np.int16)
    rows = np.broadcast_to(np.arange(n)[:, None], a.shape)
    np.add.at(used, (rows, np.where(ok, a, 0)), order)
    np.add.at(used, (rows, np.where(ok, b, 0)), order)
    hcount = np.where(live, np.maximum(_VALENCE[np.maximum(elem, 0)] - used, 0), 0)
    h_total = hcount.sum(axis=1)
    counts = np.stack([(elem == e).sum(axis=1) for e in range(len(_ELEMENTS))], 1)

    # text: every layer is a table lookup per cell, joined row by row in
    # one pass over all keys
    width = code.shape[1]
    pos = np.arange(width)[None, :]
    bond_live = pos < n_bonds[:, None]
    conn = _CONN[np.where(bond_live, code + (pos > 0) * len(_TOKENS),
                          2 * len(_TOKENS))]
    tag = (code & 1).astype(bool) & bond_live
    later = np.cumsum(tag, axis=1) > 1
    tags = _TAGS[np.where(tag, pos + later * _WIDTH, 2 * _WIDTH)]
    atom = np.arange(_MAX_ATOMS)[None, :]
    hs = _HS[np.where(live, hcount + (atom > 0) * 8, 16)]
    el = _SYMBOLS[np.where(live, elem, len(_ELEMENTS))]
    # Hill order: C, H, then the rest alphabetically; a zero count is ""
    formula = np.stack([_COUNTS[0][counts[:, 0]], _H_COUNTS[h_total]]
                       + [_COUNTS[e][counts[:, e]] for e in _ALPHA], axis=1)
    parts = (_rows(formula), _rows(el), _rows(conn), _rows(hs), _rows(tags))
    return [f"InChI=1S/{f}/e{e}/c{c}/h{h}{t}" for f, e, c, h, t in zip(*parts)]


def _rows(cells: np.ndarray) -> List[str]:
    """Each row's cells concatenated (cells hold no newline)."""
    flat = np.concatenate([cells, np.full((len(cells), 1), "\n", object)], 1)
    return "".join(flat.ravel().tolist()).split("\n")[:-1]


def digests(keys: Sequence[str]) -> np.ndarray:
    """blake2b-64 of each key, big-endian, as the store digests it."""
    return np.fromiter(
        (int.from_bytes(hashlib.blake2b(k.encode(), digest_size=8).digest(), "big")
         for k in keys),
        dtype=np.uint64, count=len(keys),
    )
