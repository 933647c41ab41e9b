"""A run of each cell with the timed path broken underneath comes out not
correct; as committed, and at a size a test can hold, it comes out
correct.  The look for a chip is skipped: these drive ``run_cell``."""
import dataclasses
import time
import types

import pytest

from bench import harness
from bench.control import StopShort

SMALL = {
    "lubm-uba-l.load": lambda c: c["profile"].update(departments=[1, 1]),
    "orb-tc-d50.load": lambda c: c.update(nodes=60),
}
CELLS = sorted(SMALL)


class Unchanged(harness.System):
    """A step that returns its state unchanged: nothing is derived."""

    def __init__(self, config, rules):
        super().__init__(config, rules)
        self.engine.materialize = lambda kb, **kw: types.SimpleNamespace(
            extra={"fused": True}, rounds=0)


class HalfBatch(harness.System):
    """Half of the batch left out: the first half of each base table."""

    def __init__(self, config, rules):
        super().__init__(config, rules)
        full = self.engine.EngineKB.from_arrays
        self.engine.EngineKB = types.SimpleNamespace(
            from_arrays=lambda program, tables, **kw: full(
                program, {p: t[:len(t) // 2] for p, t in tables.items()},
                **kw))


class Altered(harness.System):
    """One answer altered where it is produced: the first row of the
    first two-column store gets a new term in its last column."""

    def __init__(self, config, rules):
        super().__init__(config, rules)
        self._full = self.engine.materialize
        self.engine.materialize = self._altered

    def _altered(self, kb, **kw):
        stats = self._full(kb, **kw)
        p = next(p for p, r in sorted(kb.rels.items())
                 if p in self.preds and r.arity == 2 and r.count)
        rel = kb.rels[p]
        kb.rels[p] = dataclasses.replace(
            rel, data=rel.data.at[0, 1].set(kb.dict.encode("altered")))
        return stats


def run(cell, cls, seed=3):
    import jax
    loaded = harness.load_cell(cell)
    SMALL[cell](loaded["config"])
    system = cls(loaded["config"], loaded["rules"])
    return harness.run_cell(jax, loaded, seed, 0.2, False,
                            time.perf_counter(), system=system)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = run(cell, harness.System, seed=2**33 + 5)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    # the CPU reports no device memory, so peak_hbm_bytes is left out
    assert set(r["metrics"]) == {"facts_per_s", "setup_s"}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [Unchanged, HalfBatch, Altered, StopShort],
                         ids=lambda c: c.__name__)
def test_fault_is_not_correct(cell, fault):
    r = run(cell, fault)
    assert not r["correct"]
    assert any(v["value"] > v["limit"] for v in r["checks"].values())
