"""Property-based catalog invariants (hypothesis)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.catalog import Catalog, MergeConflict
from repro.io import ObjectStore

table_names = st.sampled_from(["a", "b", "c", "d", "e"])
ops = st.lists(
    st.tuples(table_names, st.integers(0, 99)), min_size=1, max_size=8
)


@given(main_ops=ops, feat_ops=ops)
@settings(max_examples=25, deadline=None)
def test_property_disjoint_merges_never_conflict(tmp_path_factory, main_ops, feat_ops):
    """Two branches editing DISJOINT table sets always merge, and the
    merge result is exactly the union of both branches' final states."""
    catalog = Catalog(ObjectStore(tmp_path_factory.mktemp("cat")))
    main_tables = {f"m_{t}" for t, _ in main_ops}
    feat_tables = {f"f_{t}" for t, _ in feat_ops}
    catalog.create_branch("feat")
    for t, v in main_ops:
        catalog.commit("main", {f"m_{t}": f"v{v}"})
    for t, v in feat_ops:
        catalog.commit("feat", {f"f_{t}": f"v{v}"})
    catalog.merge("feat", "main")
    merged = catalog.tables(branch="main")
    assert set(merged) == main_tables | feat_tables
    # last-writer-wins within each branch
    for t, v in main_ops:
        pass
    final_main = {f"m_{t}": f"v{v}" for t, v in main_ops}
    final_feat = {f"f_{t}": f"v{v}" for t, v in feat_ops}
    # (later ops overwrite earlier ones in insertion order)
    for t, v in main_ops:
        final_main[f"m_{t}"] = f"v{v}"
    for t, v in feat_ops:
        final_feat[f"f_{t}"] = f"v{v}"
    for k, v in {**final_main, **final_feat}.items():
        assert merged[k] == v


@given(edits=ops)
@settings(max_examples=25, deadline=None)
def test_property_time_travel_is_total_history(tmp_path_factory, edits):
    """Every historical commit resolves every table to exactly the value
    it had at that commit (no retroactive mutation)."""
    catalog = Catalog(ObjectStore(tmp_path_factory.mktemp("tt")))
    snapshots = []
    state = {}
    for t, v in edits:
        state[t] = f"v{v}"
        c = catalog.commit("main", {t: f"v{v}"})
        snapshots.append((c.commit_id, dict(state)))
    for cid, expected in snapshots:
        for t, v in expected.items():
            assert catalog.table_key(t, commit_id=cid) == v


@given(shared=table_names, v1=st.integers(0, 9), v2=st.integers(10, 19))
@settings(max_examples=15, deadline=None)
def test_property_conflicts_always_detected(tmp_path_factory, shared, v1, v2):
    catalog = Catalog(ObjectStore(tmp_path_factory.mktemp("cf")))
    catalog.commit("main", {shared: "base"})
    catalog.create_branch("feat")
    catalog.commit("feat", {shared: f"v{v1}"})
    catalog.commit("main", {shared: f"v{v2}"})
    with pytest.raises(MergeConflict):
        catalog.merge("feat", "main")
    # and main's value is untouched after the failed merge
    assert catalog.table_key(shared) == f"v{v2}"
