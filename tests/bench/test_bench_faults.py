"""Faults planted in the benchmark's timed path, and each cell's control.

Each fault a cell can have (an answer or a token altered where the
program produces it) must make a ``--rehearse`` run read ``correct``
false; each cell's control (its plain reference one precision down, or
with one guarantee broken) must pass at least one limit while the
program's own readings of the same run stay inside.  The chip readings
that set the limits are in ``PERF.md``.
"""

from __future__ import annotations

import numpy as np
import pytest

from test_bench_harness import CELLS, harness  # noqa: F401


# -- faults planted in the timed path must read as not correct -------------


def _fault_lookup(monkeypatch):
    from repro.core.store import IndexStore

    orig = IndexStore.lookup_batch

    def moved(self, *a, **kw):       # one byte off for every hit
        fid, off, hit = orig(self, *a, **kw)
        return fid, np.where(hit, off + 1, off), hit

    monkeypatch.setattr(IndexStore, "lookup_batch", moved)


def _fault_token(monkeypatch):
    import jax.numpy as jnp

    from repro.serve.scheduler import ContinuousEngine

    def worst(self, logits, seed):   # the first token: the least likely one
        return int(jnp.argmin(logits[0]))

    monkeypatch.setattr(ContinuousEngine, "_first_token", worst)


FAULTS = {
    "pubchem-index.lookup-zipf": _fault_lookup,
    "yi-6b-1chip.chat": _fault_token,
}


@pytest.mark.parametrize("workload", sorted(FAULTS))
def test_answer_altered_where_produced_is_not_correct(harness, monkeypatch,
                                                      workload):
    FAULTS[workload](monkeypatch)
    rc, result, err = harness(workload)
    assert rc == 0, err
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


# -- each cell's control reads as not correct ------------------------------


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(harness, workload):
    """The reference in the program's place, one precision down (or with
    one guarantee broken), read by ``--control 1`` after the check: at
    least one number passes its limit, while the program's own readings of
    the same run stay inside."""
    rc, result, err = harness(workload, extra=["--control", "1"])
    assert rc == 0, err
    assert result["correct"] is True, result["checks"]
    control = result["control"]
    assert control.keys() <= result["checks"].keys()
    assert any(c["value"] > c["limit"] for c in control.values()), control
    # the numbers the run is judged by still come last
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")
