"""Plain reference of the pubchem-index deployment, and its controls.

Written apart from the program and importing nothing of it: a stored key
is found at the location it was published with, an absent key is found
nowhere.

The control breaks the configuration's guarantee that lookups are exact
(an absent key is never reported present), the step a later change might
be tempted to take, and must read as not correct: :func:`bloom_only_lookup`
answers membership from a Bloom filter of the configuration's 12 bits per
key, with no digest probe or key verify.
"""

from __future__ import annotations

import hashlib
from typing import List

import numpy as np

_SM_GAMMA = np.uint64(0x9E3779B97F4A7C15)
_SM_MUL1 = np.uint64(0xBF58476D1CE4E5B9)
_SM_MUL2 = np.uint64(0x94D049BB133111EB)


def expected_locations(key_numbers: np.ndarray, n_present: int,
                       ref_fid: np.ndarray, ref_off: np.ndarray):
    """``(hit, file_id, offset)`` each key number should read back."""
    hit = key_numbers < n_present
    safe = np.where(hit, key_numbers, 0)
    return hit, ref_fid[safe], ref_off[safe]


def _mix64(x: np.ndarray) -> np.ndarray:
    z = x + _SM_GAMMA
    z = (z ^ (z >> np.uint64(30))) * _SM_MUL1
    z = (z ^ (z >> np.uint64(27))) * _SM_MUL2
    return z ^ (z >> np.uint64(31))


def bloom_only_lookup(keys: List[str], key_numbers: np.ndarray,
                      n_present: int, ref_fid: np.ndarray, ref_off: np.ndarray,
                      bits_per_key: int):
    """The control for lookups: a key the Bloom filter passes reads as
    present, at the location of the stored key it was mistaken for (the
    first stored key), with no probe and no key verify.  The filter has
    exactly ``bits_per_key`` bits a stored key and ``round(bits_per_key *
    ln 2)`` hashes: about 0.3 % false positives at 12 bits."""
    d = np.array([int.from_bytes(hashlib.blake2b(k.encode(), digest_size=8)
                                 .digest(), "big") for k in keys], np.uint64)
    m = np.uint64(n_present * bits_per_key)
    n_hash = max(1, int(round(bits_per_key * np.log(2))))
    h2 = _mix64(d) | np.uint64(1)
    plane = np.zeros(int(m), bool)
    for i in range(n_hash):
        plane[((d[:n_present] + np.uint64(i) * h2[:n_present]) % m).astype(np.int64)] = True
    passed = np.ones(len(d), bool)
    for i in range(n_hash):
        passed &= plane[((d + np.uint64(i) * h2) % m).astype(np.int64)]
    hit = passed[key_numbers]
    safe = np.where(key_numbers < n_present, key_numbers, 0)
    return hit, ref_fid[safe], ref_off[safe]
