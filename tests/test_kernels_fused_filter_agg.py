"""fused_filter_agg Pallas kernel vs jnp oracle (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.fused_filter_agg import fused_filter_agg, fused_filter_agg_ref


def make_inputs(n, num_groups, rng, dtype=np.float32):
    return (
        rng.integers(0, num_groups, n).astype(np.int32),
        rng.standard_normal(n).astype(dtype),
        (rng.random(n) * 100).astype(dtype),
    )


@pytest.mark.parametrize("n", [128, 1024, 1000, 4096, 5000])
@pytest.mark.parametrize("num_groups", [64, 256])
def test_shapes_sweep(n, num_groups, rng):
    keys, vals, filt = make_inputs(n, num_groups, rng)
    got_s, got_c = fused_filter_agg(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(filt),
        op="ge", threshold=50.0, num_groups=num_groups,
    )
    exp_s, exp_c = fused_filter_agg_ref(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(filt),
        op="ge", threshold=50.0, num_groups=num_groups,
    )
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(exp_s), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(exp_c))


@pytest.mark.parametrize("op", ["ge", "gt", "le", "lt", "eq", "ne"])
def test_ops_sweep(op, rng):
    keys, vals, filt = make_inputs(2048, 128, rng)
    filt = np.round(filt)  # make eq/ne meaningful
    got_s, got_c = fused_filter_agg(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(filt),
        op=op, threshold=42.0, num_groups=128,
    )
    exp_s, exp_c = fused_filter_agg_ref(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(filt),
        op=op, threshold=42.0, num_groups=128,
    )
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(exp_s), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(exp_c))


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_dtypes_sweep(dtype, rng):
    keys = rng.integers(0, 64, 1024).astype(np.int32)
    vals = rng.integers(-5, 5, 1024).astype(dtype)
    filt = rng.integers(0, 10, 1024).astype(np.float32)
    got_s, got_c = fused_filter_agg(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(filt),
        op="gt", threshold=4.0, num_groups=64,
    )
    exp_s, exp_c = fused_filter_agg_ref(
        jnp.asarray(keys), jnp.asarray(vals).astype(jnp.float32), jnp.asarray(filt),
        op="gt", threshold=4.0, num_groups=64,
    )
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(exp_s), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(exp_c))


def test_empty_selection(rng):
    keys, vals, filt = make_inputs(512, 128, rng)
    got_s, got_c = fused_filter_agg(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(filt),
        op="ge", threshold=1e9, num_groups=128,
    )
    assert np.asarray(got_s).sum() == 0 and np.asarray(got_c).sum() == 0


def test_matches_query_engine_groupby(rng):
    """Cross-check: kernel == engine's sort-based groupby on the same data."""
    from repro.engine import Columnar, Query, col, execute_query

    keys, vals, filt = make_inputs(2000, 32, rng)
    rel = Columnar.from_numpy({"k": keys, "v": vals, "f": filt})
    q = Query("t").where(col("f") >= 50.0).group_by("k").agg("sum", col("v"), "s").count("n")
    eng = execute_query(q, rel).to_numpy()
    got_s, got_c = fused_filter_agg(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(filt),
        op="ge", threshold=50.0, num_groups=32,
    )
    got_s, got_c = np.asarray(got_s), np.asarray(got_c)
    for i, key in enumerate(eng["k"]):
        np.testing.assert_allclose(got_s[key], eng["s"][i], rtol=1e-4, atol=1e-4)
        assert got_c[key] == eng["n"][i]


@given(
    n=st.integers(1, 3000),
    g=st.sampled_from([128, 256]),
    threshold=st.floats(-2, 2, allow_nan=False),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=15, deadline=None)
def test_property_kernel_equals_oracle(n, g, threshold, seed):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, g, n).astype(np.int32)
    vals = rng.standard_normal(n).astype(np.float32)
    filt = rng.standard_normal(n).astype(np.float32)
    got_s, got_c = fused_filter_agg(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(filt),
        op="lt", threshold=threshold, num_groups=g,
    )
    exp_s, exp_c = fused_filter_agg_ref(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(filt),
        op="lt", threshold=threshold, num_groups=g,
    )
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(exp_s), rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(exp_c))
