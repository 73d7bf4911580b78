"""TensorTable format: snapshots, sharding, stats, scan pruning."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.io import ObjectStore
from repro.table import Predicate, Schema, TableFormat, execute_scan, plan_scan


def make_table(n, rng):
    return {
        "pickup_location_id": rng.integers(0, 256, n).astype(np.int32),
        "passenger_count": rng.integers(0, 8, n).astype(np.int32),
        "fare": (rng.random(n) * 100).astype(np.float32),
    }


SCHEMA = Schema.of(
    pickup_location_id="int32", passenger_count="int32", fare="float32"
)


def test_write_read_roundtrip(fmt, rng):
    data = make_table(1000, rng)
    snap = fmt.write("taxi_table", SCHEMA, data)
    assert snap.num_rows == 1000
    assert len(snap.shards) == 8  # 1000 rows / 128 shard_rows
    out = fmt.read(snap)
    for col in data:
        np.testing.assert_array_equal(out[col], data[col])


def test_append_shares_parent_shards(fmt, rng):
    d1 = make_table(256, rng)
    s1 = fmt.write("t", SCHEMA, d1)
    d2 = make_table(128, rng)
    s2 = fmt.write("t", SCHEMA, d2, parent=s1, append=True)
    assert s2.num_rows == 384
    assert s2.parent_id == s1.snapshot_id
    assert list(s2.shards[: len(s1.shards)]) == list(s1.shards)  # structural sharing
    out = fmt.read(s2)
    np.testing.assert_array_equal(
        out["fare"], np.concatenate([d1["fare"], d2["fare"]])
    )


def test_time_travel_via_manifest_keys(fmt, rng):
    d1 = make_table(64, rng)
    s1 = fmt.write("t", SCHEMA, d1)
    k1 = fmt.manifest_key(s1)
    d2 = make_table(64, rng)
    s2 = fmt.write("t", SCHEMA, d2)
    old = fmt.load_snapshot(k1)
    np.testing.assert_array_equal(fmt.read(old)["fare"], d1["fare"])
    assert old.snapshot_id == s1.snapshot_id != s2.snapshot_id


def test_scan_column_pruning(fmt, rng):
    snap = fmt.write("t", SCHEMA, make_table(512, rng))
    plan = plan_scan(snap, columns=["fare"])
    assert plan.columns == ["fare"]
    assert plan.pruned_columns == 2
    out = execute_scan(fmt, plan)
    assert set(out) == {"fare"}


def test_scan_shard_pruning_with_sorted_column(fmt):
    n = 1024
    data = {
        "pickup_location_id": np.arange(n, dtype=np.int32),
        "passenger_count": np.ones(n, dtype=np.int32),
        "fare": np.ones(n, dtype=np.float32),
    }
    snap = fmt.write("t", SCHEMA, data)  # 8 shards of 128 sorted ids
    plan = plan_scan(
        snap, predicates=[Predicate("pickup_location_id", ">=", 900)]
    )
    assert plan.pruned_shards == 7  # only the last shard can match
    out = execute_scan(fmt, plan)
    assert (out["pickup_location_id"] >= 900).all()
    assert len(out["pickup_location_id"]) == n - 900


def test_scan_returns_only_projection(fmt, rng):
    """Regression: predicate columns are read for filtering but must NOT
    leak into the result when the caller didn't project them."""
    data = make_table(300, rng)
    snap = fmt.write("t", SCHEMA, data)
    plan = plan_scan(
        snap,
        columns=["fare"],
        predicates=[Predicate("passenger_count", ">", 3)],
    )
    assert "passenger_count" in plan.columns  # read for filtering...
    assert plan.projection == ["fare"]
    out = execute_scan(fmt, plan)
    assert set(out) == {"fare"}  # ...but dropped from the result
    np.testing.assert_array_equal(
        out["fare"], data["fare"][data["passenger_count"] > 3]
    )
    # the all-shards-pruned path honours the projection too
    empty = execute_scan(
        fmt,
        plan_scan(
            snap,
            columns=["fare"],
            predicates=[Predicate("passenger_count", ">", 1000)],
        ),
    )
    assert set(empty) == {"fare"} and len(empty["fare"]) == 0


def test_parallel_shard_reads_match_serial(fmt, rng):
    """execute_scan(pool=...) preserves shard order: byte-identical
    output to the serial read, residual filter included."""
    from concurrent.futures import ThreadPoolExecutor

    data = make_table(1500, rng)  # ~12 shards at 128 rows
    snap = fmt.write("t", SCHEMA, data)
    plan = plan_scan(snap, predicates=[Predicate("fare", "<", 50.0)])
    serial = execute_scan(fmt, plan)
    with ThreadPoolExecutor(max_workers=4) as pool:
        pooled = execute_scan(fmt, plan, pool=pool)
    assert set(serial) == set(pooled)
    for c in serial:
        np.testing.assert_array_equal(serial[c], pooled[c])


def test_scan_residual_predicate_exact(fmt, rng):
    data = make_table(300, rng)
    snap = fmt.write("t", SCHEMA, data)
    plan = plan_scan(
        snap,
        columns=["fare"],
        predicates=[Predicate("passenger_count", ">", 3)],
    )
    out = execute_scan(fmt, plan)
    expected = data["fare"][data["passenger_count"] > 3]
    np.testing.assert_array_equal(out["fare"], expected)


def test_content_fingerprint_invariant_to_shard_layout(fmt, rng):
    """The differential-cache input identity: same rows in the same order
    -> same content fingerprint, regardless of shard boundaries (what
    keeps the cache warm across `repro compact`)."""
    data = make_table(1000, rng)
    snap = fmt.write("t", SCHEMA, data)
    wide = TableFormat(fmt.store, shard_rows=1000)
    resharded = wide.write("t", SCHEMA, data)
    assert resharded.snapshot_id != snap.snapshot_id  # layout differs...
    assert fmt.content_fingerprint(resharded) == fmt.content_fingerprint(snap)
    # ...but content identity is the same; compaction is the same story
    compacted, merged = wide.compact_snapshot(snap, target_rows=500)
    assert merged > 0
    assert fmt.content_fingerprint(compacted) == fmt.content_fingerprint(snap)
    # different data (or order) is a different identity
    reordered = {c: v[::-1].copy() for c, v in data.items()}
    other = fmt.write("t", SCHEMA, reordered)
    assert fmt.content_fingerprint(other) != fmt.content_fingerprint(snap)
    # memoized: the second call is a ref read, not a table scan
    gets_before = fmt.store.stats.gets
    fmt.content_fingerprint(snap)
    assert fmt.store.stats.gets == gets_before


def test_schema_validation_errors(fmt, rng):
    data = make_table(10, rng)
    bad = dict(data)
    bad["fare"] = bad["fare"].astype(np.float64)
    with pytest.raises(TypeError):
        fmt.write("t", SCHEMA, bad)
    with pytest.raises(ValueError):
        fmt.write("t", SCHEMA, {k: v[:5] if k == "fare" else v for k, v in data.items()})


@given(
    n=st.integers(0, 500),
    threshold=st.integers(-5, 260),
    op=st.sampled_from(["<", "<=", ">", ">=", "==", "!="]),
)
@settings(max_examples=40, deadline=None)
def test_property_pushdown_equals_posthoc_filter(tmp_path_factory, n, threshold, op):
    """Pushdown (stats pruning + residual) == filtering after a full read."""
    fmt = TableFormat(ObjectStore(tmp_path_factory.mktemp("pp")), shard_rows=64)
    # threshold may be negative; keep the seed non-negative
    rng = np.random.default_rng(1000 + n + threshold + len(op))
    data = make_table(n, rng)
    snap = fmt.write("t", SCHEMA, data)
    pred = Predicate("pickup_location_id", op, threshold)
    out = execute_scan(fmt, plan_scan(snap, predicates=[pred]))
    full = fmt.read(snap)
    mask = pred.mask(full["pickup_location_id"]) if n else np.zeros(0, bool)
    for col in SCHEMA.names:
        np.testing.assert_array_equal(out[col], full[col][mask])
