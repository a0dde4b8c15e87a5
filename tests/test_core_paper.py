"""Core paper-system tests: identifiers, records, index, extraction,
collisions, intersection — unit + hypothesis property tests.
"""

import tempfile
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    ByteOffsetIndex,
    RecordStore,
    birthday_expectation,
    build_index,
    canonical_id,
    canonical_id_from_structure,
    collisions_from_pairs,
    extract,
    hashed_key,
    intersect_host,
    intersect_sorted,
    iter_record_offsets,
    iter_records,
    molecule_from_cid,
    naive_scan,
    pack_ids,
    read_record_at,
    scan_corpus,
    scan_pairs_sorted,
    unpack_ids,
)
from repro.core.records import extract_property, record_properties
from repro.core.sdfgen import (
    PROP_ID,
    PROP_KEY,
    CorpusSpec,
    db_id_list,
    generate_corpus,
    ground_truth_final_dataset,
    ground_truth_intersection,
    record_text_for_cid,
)


@pytest.fixture(scope="module")
def corpus():
    spec = CorpusSpec(n_files=3, records_per_file=500, key_bits=24)
    root = Path(tempfile.mkdtemp()) / "corpus"
    generate_corpus(root, spec)
    return RecordStore(root), spec


# ---------------------------------------------------------------------------
# identifiers
# ---------------------------------------------------------------------------

def test_canonical_id_deterministic_and_injective():
    ids = [canonical_id(molecule_from_cid(c)) for c in range(3000)]
    assert len(set(ids)) == 3000
    assert ids[7] == canonical_id(molecule_from_cid(7))


def test_hashed_key_format_and_truncation():
    k = hashed_key("InChI=1S/C2H6O/c1-2-3/h3H,2H2,1H3")
    assert len(k) == 27 and k[14] == "-" and k.endswith("SA-N")
    k8 = {hashed_key(f"id{i}", bits=8) for i in range(1000)}
    assert len(k8) <= 256  # 8-bit space cannot exceed 256 keys


def test_recompute_from_structure_roundtrip():
    for cid in range(0, 200, 17):
        spec = CorpusSpec()
        text = record_text_for_cid(cid, spec)
        assert canonical_id_from_structure(text) == extract_property(text, PROP_ID)


@settings(max_examples=40, deadline=None)
@given(cid=st.integers(0, 4**15 - 1))
def test_molecule_structural_validity(cid):
    mol = molecule_from_cid(cid)
    n = mol.natoms
    for a, b, order, stereo in mol.bonds:
        assert 0 <= a < b < n
        assert order in (1, 2)
    assert all(h >= 0 for h in mol.hcount)
    # connected: union-find over bonds
    parent = list(range(n))
    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x
    for a, b, _, _ in mol.bonds:
        parent[find(a)] = find(b)
    assert len({find(i) for i in range(n)}) == 1


@settings(max_examples=30, deadline=None)
@given(ids=st.lists(st.text(
    alphabet=st.characters(min_codepoint=33, max_codepoint=126), min_size=1,
    max_size=60), min_size=1, max_size=40))
def test_packing_roundtrip(ids):
    assert unpack_ids(pack_ids(ids)) == ids


# ---------------------------------------------------------------------------
# records / index
# ---------------------------------------------------------------------------

def test_record_iteration_and_seek(corpus):
    store, spec = corpus
    path = store.files()[0]
    records = list(iter_records(path))
    assert len(records) == spec.records_per_file
    # every recorded offset seeks back to the identical record
    for off, text in records[:: max(1, len(records) // 23)]:
        assert read_record_at(path, off) == text
    offs = list(iter_record_offsets(path))
    assert offs == [o for o, _ in records]


def test_index_build_serial_parallel_equal(corpus):
    store, spec = corpus
    i1 = build_index(store, workers=1)
    i2 = build_index(store, workers=2)
    assert i1.entries == i2.entries
    assert len(i1) == spec.n_records
    assert i1.stats.n_duplicate_keys == 0  # full ids are injective


def test_index_csv_roundtrip(corpus, tmp_path):
    store, _ = corpus
    idx = build_index(store)
    size = idx.save_csv(tmp_path / "ix.csv")
    assert size > 0
    back = ByteOffsetIndex.load_csv(tmp_path / "ix.csv")
    assert back.entries == idx.entries


def test_index_lookup_matches_linear_scan(corpus):
    store, _ = corpus
    idx = build_index(store)
    path = store.files()[1]
    for off, text in list(iter_records(path))[:40]:
        key = extract_property(text, PROP_ID)
        assert idx.lookup(key) == (path.name, off)


# ---------------------------------------------------------------------------
# extraction (Algorithm 3) + baseline (Algorithm 1)
# ---------------------------------------------------------------------------

def test_extraction_funnel_exact(corpus):
    store, spec = corpus
    idx = build_index(store)
    targets = intersect_host(
        db_id_list(spec, "chembl", extra_outside=10),
        db_id_list(spec, "emolecules", extra_outside=10),
    ).ids
    res = extract(store, idx, targets)
    assert res.found == len(ground_truth_intersection(spec))
    assert len(res.missing) == 10  # the outside-universe ids
    assert not res.mismatches
    # grouped: opens ≤ files, seeks == found
    assert res.files_opened <= len(store)
    assert res.seeks == res.found


def test_extraction_sorted_offsets_are_forward(corpus):
    store, spec = corpus
    idx = build_index(store)
    from repro.core.extract import plan_extraction

    targets = db_id_list(spec, "chembl")[:100]
    plan, _ = plan_extraction(idx, targets)
    for fname, items in plan.items():
        offs = [o for _, _, o in items]
        assert offs == sorted(offs)


def test_baseline_agrees_with_extraction(corpus):
    store, spec = corpus
    idx = build_index(store)
    targets = db_id_list(spec, "chembl")[:25]
    res_naive = naive_scan(store, targets, membership="set")
    res_idx = extract(store, idx, targets)
    assert set(res_naive.records) == set(res_idx.records)
    for k in res_naive.records:
        assert res_naive.records[k].strip() == res_idx.records[k].strip()


def test_ungrouped_extraction_equivalent(corpus):
    store, spec = corpus
    idx = build_index(store)
    targets = db_id_list(spec, "emolecules")[:30]
    a = extract(store, idx, targets, group_by_file=True)
    b = extract(store, idx, targets, group_by_file=False)
    assert a.records == b.records
    assert b.files_opened >= a.files_opened


# ---------------------------------------------------------------------------
# collisions (§VI)
# ---------------------------------------------------------------------------

def test_collision_scan_matches_dict_and_sorted_paths(corpus):
    store, _ = corpus
    rep = scan_corpus(store, key_bits=16)
    # independent sorted-path implementation agrees
    pairs = []
    for p in store.files():
        for _off, text in iter_records(p):
            fid = extract_property(text, PROP_ID)
            pairs.append((hashed_key(fid, 16), fid))
    sorted_path = scan_pairs_sorted([k for k, _ in pairs], [v for _, v in pairs])
    assert rep.colliding == sorted_path
    # birthday-bound order of magnitude (n=1500 at 16 bits => E≈17)
    e = birthday_expectation(rep.n_records, 16)
    assert 0.2 * e <= rep.n_colliding_keys <= 5 * e


def test_hashed_pipeline_mismatches_full_pipeline_clean(corpus):
    store, spec = corpus
    targets = db_id_list(spec, "chembl")
    idx_h = build_index(store, key_mode="hashed_key", key_bits=16, recompute_keys=True)
    res_h = extract(store, idx_h, targets, key_bits=16)
    idx_f = build_index(store, key_mode="full_id")
    res_f = extract(store, idx_f, targets)
    assert not res_f.mismatches
    assert res_f.found >= res_h.found
    # at 16 bits over 1500 records, shadowing must have occurred
    assert idx_h.stats.n_duplicate_keys > 0


def test_collisions_from_pairs_distinctness():
    pairs = [("K1", "a"), ("K1", "a"), ("K2", "a"), ("K2", "b"), ("K3", "c")]
    got = collisions_from_pairs(pairs)
    assert got == {"K2": ["a", "b"]}  # duplicates of same id are NOT collisions


def test_verification_reports_each_collision_as_a_mismatch(corpus):
    """§VI.A's discovery path: with 16-bit hashed keys some targets resolve
    to another molecule's record; verification reports each as a mismatch,
    never as a record, and each is a true key collision.  The full-id
    pipeline (§VI.C) returns every target, clean."""
    store, spec = corpus
    targets = db_id_list(spec, "chembl")
    colliding = scan_corpus(store, key_bits=16).colliding
    idx_h = build_index(store, key_mode="hashed_key", key_bits=16,
                        recompute_keys=True)
    res_h = extract(store, idx_h, targets, key_bits=16)
    assert res_h.mismatches
    assert res_h.found + len(res_h.mismatches) == len(targets)
    for m in res_h.mismatches:
        assert m.expected_id != m.found_id
        assert m.expected_id not in res_h.records
        assert hashed_key(m.expected_id, 16) == m.lookup_key
        assert hashed_key(m.found_id, 16) == m.lookup_key
        assert {m.expected_id, m.found_id} <= set(colliding[m.lookup_key])
    res_f = extract(store, build_index(store, key_mode="full_id"), targets)
    assert res_f.found == len(targets)
    assert not res_f.mismatches and not res_f.missing


def test_collisions_shrink_with_key_width(corpus):
    """Eq. 4-5: keys of every width truncate one digest, so ids that collide
    at b bits collide at every narrower width; at the paper's 50 bits
    (h ≈ 1e15, E = n²/2h ≈ 1e-9 here) this corpus has none."""
    store, _ = corpus
    affected = [
        set().union(*scan_corpus(store, key_bits=b).colliding.values())
        for b in (16, 20, 24, 28, 50)
    ]
    assert affected[0]
    for narrow, wide in zip(affected, affected[1:]):
        assert wide <= narrow
    assert not affected[-1]


def test_full_id_index_costs_more_storage_than_hashed_keys(corpus, tmp_path):
    """Table IV's storage column: the hashed key is a fixed 27 characters,
    every full id is longer, so the same records' full-id index is the
    larger one on disk — the price of deterministic uniqueness."""
    store, spec = corpus
    idx_h = build_index(store, key_mode="hashed_key")
    idx_f = build_index(store, key_mode="full_id")
    assert len(idx_h) == len(idx_f) == spec.n_records
    assert {len(k) for k in idx_h.entries} == {27}
    assert min(len(k) for k in idx_f.entries) > 27
    assert idx_f.save_csv(tmp_path / "f.csv") > idx_h.save_csv(tmp_path / "h.csv")


# ---------------------------------------------------------------------------
# intersection (Eq. 1)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(
    a=st.lists(st.integers(0, 400), max_size=120),
    b=st.lists(st.integers(0, 400), max_size=120),
    c=st.lists(st.integers(0, 400), max_size=120),
)
def test_intersection_paths_agree_with_sets(a, b, c):
    la = [f"id{x}" for x in a]
    lb = [f"id{x}" for x in b]
    lc = [f"id{x}" for x in c]
    want = sorted(set(la) & set(lb) & set(lc))
    assert intersect_host(la, lb, lc).ids == want
    assert intersect_sorted(la, lb, lc).ids == want


def test_funnel_counts_reproduce_paper_shape(corpus):
    """db_final ⊂ extracted ⊂ targets ⊂ universe, all counts exact."""
    store, spec = corpus
    gt = ground_truth_intersection(spec)
    gtf = ground_truth_final_dataset(spec)
    assert len(gtf) <= len(gt) <= spec.n_records
    idx = build_index(store)
    targets = intersect_host(
        db_id_list(spec, "chembl"), db_id_list(spec, "emolecules")
    ).ids
    res = extract(store, idx, targets)
    assert res.found == len(gt)
