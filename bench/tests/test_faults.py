"""A run with its timed path broken underneath comes out not correct.

Each test skips only the look for a chip and drives the rest of a run at
a tiny size, with one fault planted in the program: an answer altered
where it is produced, half of the rows left out of every scan, and a
pipeline run that leaves the branch's state unchanged.  (The engine runs
on one chip, so no exchange between chips can be left out.)
"""
import pytest

import tiny


def _alter_answers(monkeypatch):
    from repro.engine.columnar import Columnar

    produce = Columnar.to_numpy

    def altered(self, *, compact=True):
        out = produce(self, compact=compact)
        for name in sorted(out):
            if len(out[name]):  # the first cell: one more, or 1% more
                out[name] = out[name].copy()
                out[name][0] += 1 if out[name].dtype.kind in "iu" else out[name][0] / 100
                break
        return out

    monkeypatch.setattr(Columnar, "to_numpy", altered)


def _drop_half_the_rows(monkeypatch):
    import repro.core.runner as runner

    scan = runner.execute_scan

    def half(*args, **kwargs):
        out = scan(*args, **kwargs)
        return {c: v[: len(v) // 2] for c, v in out.items()}

    monkeypatch.setattr(runner, "execute_scan", half)


def _leave_state_unchanged(monkeypatch):
    from repro.catalog.nessie import Catalog

    def no_merge(self, source, target, **kwargs):
        if kwargs.get("delete_source"):
            self.delete_branch(source)
        return self.head(target)

    monkeypatch.setattr(Catalog, "merge", no_merge)


FAULTS = {"answer_altered": _alter_answers, "half_the_rows": _drop_half_the_rows,
          "state_unchanged": _leave_state_unchanged}
CASES = [(cell, fault) for cell in tiny.CELLS for fault in FAULTS
         if fault != "state_unchanged" or cell == "taxi.pipeline"]


@pytest.mark.parametrize("cell, fault", CASES)
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    result = tiny.run(cell)
    assert result["correct"] is False, result["compared"]
    assert any(c["value"] > c["limit"] for c in result["compared"].values())
