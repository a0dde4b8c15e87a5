"""Point lookups of full keys through ``QueryService.lookup_batch``.

Each request is ``keys_per_request`` key numbers: ``< n_present`` are
stored keys, Zipfian over a seeded scramble of the key space (YCSB's
scrambled Zipfian), the rest absent keys, uniform.  After the window
every key of every request sent in it is compared with where it was
published.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.workload import zipf_ranks

span = "bench.lookup"


def streams(p: dict, sizes: Dict[str, int], rng) -> List[np.ndarray]:
    n_present, n_absent = sizes["n_present"], sizes["n_absent"]
    shape = (p["clients"], p["requests_per_client"], p["keys_per_request"])
    scramble = rng.permutation(n_present)
    keys = scramble[zipf_ranks(rng, n_present, p["zipf_theta"], shape)]
    absent = rng.random(shape) < p["absent_share"]
    keys = np.where(absent, n_present + rng.integers(0, n_absent, shape), keys)
    return list(keys)


def call(state):
    svc, keys = state.svc, state.keys

    def lookup(req):
        return svc.lookup_batch([keys[i] for i in req])

    return lookup


def warm(ctx, state, store, probe: str) -> None:
    """Every probe shape the traffic reaches: all shards hold the same
    number of rows, so a probe's shape varies only with the number of keys
    that reach one shard, 1 .. the keys in flight (at most the
    configuration's ``warm_probe_keys``)."""
    from bench import keygen

    conf, traffic = state.conf, ctx.traffic
    shards = conf["n_shards"]
    in_flight = traffic["clients"] * traffic["keys_per_request"]
    most = min(in_flight, conf["warm_probe_keys"])
    stored = state.keys[: state.n_present]
    head = stored[: most * shards * 4]
    top = keygen.digests(head) >> np.uint64(64 - (shards - 1).bit_length())
    shard0 = [k for k, s in zip(head, top.tolist()) if s == 0]
    for q in range(1, min(most, len(shard0)) + 1):
        store.lookup_batch(shard0[:q], probe=probe)


def _requests(state, sent) -> np.ndarray:
    return np.stack([np.asarray(state.streams[r.client][r.index % len(state.streams[r.client])])
                     for r in sent]) if sent else np.zeros((0, 1), np.int64)


def check(ctx, state, sent, reference) -> List[tuple]:
    """``lookup_keys_wrong``: keys whose hit, file or offset differs from
    where they were published, or that came back degraded."""
    req = _requests(state, sent)
    state.checked = sent
    fid = np.stack([r.answer[0] for r in sent]) if sent else req * 0
    off = np.stack([r.answer[1] for r in sent]) if sent else req * 0
    hit = np.stack([r.answer[2] for r in sent]) if sent else req > 0
    deg = np.stack([r.answer[3] for r in sent]) if sent else req > 0
    want_hit, want_fid, want_off = reference.expected_locations(
        req, state.n_present, state.ref_fid, state.ref_off)
    wrong = (deg | (hit != want_hit)
             | (want_hit & ((fid != want_fid) | (off != want_off))))
    return [("lookup_keys_wrong", int(wrong.sum()), 0)]


def control(ctx, state, reference) -> List[tuple]:
    """The same number with a Bloom-only answer (no probe, no key verify)
    in the program's place, on the requests :func:`check` compared."""
    req = _requests(state, state.checked)
    hit, fid, off = reference.bloom_only_lookup(
        state.keys, req, state.n_present, state.ref_fid, state.ref_off,
        state.conf["bloom_bits_per_key"])
    want_hit, want_fid, want_off = reference.expected_locations(
        req, state.n_present, state.ref_fid, state.ref_off)
    wrong = (hit != want_hit) | (want_hit & ((fid != want_fid) | (off != want_off)))
    return [("lookup_keys_wrong", int(wrong.sum()), 0)]
