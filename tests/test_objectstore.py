"""Unit + property tests for the content-addressed object store."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.io import ObjectStore, array_to_bytes, bytes_to_array


def test_put_get_roundtrip(store):
    key = store.put(b"hello lakehouse")
    assert store.get(key) == b"hello lakehouse"
    assert store.exists(key)


def test_put_is_idempotent(store):
    k1 = store.put(b"same bytes")
    bytes_before = store.stats.bytes_written
    k2 = store.put(b"same bytes")
    assert k1 == k2
    # second put counts in telemetry but file already existed
    assert store.stats.puts == 2
    assert store.stats.bytes_written == 2 * bytes_before / 2 + len(b"same bytes")


def test_corruption_detected(store, tmp_path):
    key = store.put(b"precious")
    path = store._object_path(key)
    path.write_bytes(b"tampered")
    with pytest.raises(IOError):
        store.get(key)


def test_refs_cas(store):
    store.set_ref("branches", "main", {"commit": "a"})
    assert store.compare_and_set_ref("branches", "main", {"commit": "a"}, {"commit": "b"})
    assert not store.compare_and_set_ref("branches", "main", {"commit": "a"}, {"commit": "c"})
    assert store.get_ref("branches", "main") == {"commit": "b"}


def test_ref_listing_and_delete(store):
    store.set_ref("ns", "x/y", {"v": 1})
    store.set_ref("ns", "z", {"v": 2})
    assert store.list_refs("ns") == {"x/y": {"v": 1}, "z": {"v": 2}}
    store.delete_ref("ns", "x/y")
    assert store.list_refs("ns") == {"z": {"v": 2}}


@given(
    data=st.binary(min_size=0, max_size=2048),
)
@settings(max_examples=50, deadline=None)
def test_property_content_addressing(tmp_path_factory, data):
    store = ObjectStore(tmp_path_factory.mktemp("prop"))
    key = store.put(data)
    assert store.get(key) == data


@given(
    shape=st.lists(st.integers(0, 7), min_size=1, max_size=3),
    dtype=st.sampled_from(["float32", "int32", "uint16", "float64", "bool"]),
)
@settings(max_examples=50, deadline=None)
def test_property_tensor_serialization(shape, dtype):
    rng = np.random.default_rng(42)
    arr = (rng.standard_normal(shape) * 10).astype(dtype)
    out = bytes_to_array(array_to_bytes(arr))
    assert out.dtype == arr.dtype and out.shape == arr.shape
    np.testing.assert_array_equal(out, arr)


# ----------------------------------------------------- delete + sweep (GC)
def test_delete_blob_idempotent(store):
    key = store.put(b"ephemeral")
    size = store.delete(key)
    assert size == len(b"ephemeral")
    assert not store.exists(key)
    # second delete is a safe no-op (retryable sweeps)
    assert store.delete(key) == 0


def test_delete_ref_idempotent(store):
    """Regression (ISSUE 2): delete_ref must no-op on a missing ref so
    eviction/GC sweeps can retry safely after a crash."""
    store.set_ref("ns", "victim", {"v": 1})
    assert store.delete_ref("ns", "victim") is True
    assert store.get_ref("ns", "victim") is None
    assert store.delete_ref("ns", "victim") is False
    # a ref that never existed is equally fine
    assert store.delete_ref("ns", "never_there") is False
    assert store.delete_ref("empty_namespace", "nope") is False


def test_sweep_keeps_live_objects(store):
    live = store.put(b"live data")
    dead1 = store.put(b"dead one")
    dead2 = store.put(b"dead two")
    result = store.sweep({live}, grace_s=0.0)
    assert result.swept == 2
    assert result.bytes_reclaimed == len(b"dead one") + len(b"dead two")
    assert store.exists(live)
    assert not store.exists(dead1) and not store.exists(dead2)
    assert store.stats.gc_objects_swept == 2
    assert store.stats.gc_bytes_reclaimed == result.bytes_reclaimed


def test_sweep_dry_run_reports_without_deleting(store):
    store.put(b"live")
    dead = store.put(b"doomed")
    result = store.sweep(set(), grace_s=0.0, dry_run=True)
    assert result.dry_run and result.swept == 2
    assert store.exists(dead)
    assert store.stats.gc_objects_swept == 0


def test_object_size_and_age(store):
    key = store.put(b"12345")
    assert store.object_size(key) == 5
    assert store.object_age_s(key) is not None
    assert store.object_size("00" * 16) is None
    assert store.object_age_s("00" * 16) is None
