"""Sharding rules: divisibility fallbacks, spec assignment, MoE invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro.distribution.sharding import DEFAULT_RULES
from repro.models.moe import MoEConfig, init_moe, moe_apply


class FakeMesh:
    """Just enough mesh interface for spec_for (shape lookup)."""

    def __init__(self, **axes):
        self.shape = dict(axes)


def test_attention_params_column_row_parallel():
    mesh = FakeMesh(data=16, model=16)
    spec = DEFAULT_RULES.spec_for("seg0/b0/attn/wq/w", (88, 4096, 4096), mesh)
    assert spec == P(None, "data", "model")  # stacked dim unsharded
    spec = DEFAULT_RULES.spec_for("seg0/b0/attn/wo/w", (88, 4096, 4096), mesh)
    assert spec == P(None, "model", "data")


def test_experts_prefer_ep_then_fall_back_to_tp():
    mesh = FakeMesh(data=16, model=16)
    # 256 experts % 16 == 0 -> EP
    spec = DEFAULT_RULES.spec_for(
        "seg1/b0/moe/experts/gate", (58, 256, 7168, 2048), mesh
    )
    assert spec == P(None, "model", "data", None)
    # 60 experts % 16 != 0 -> expert-internal TP on d_ff
    spec = DEFAULT_RULES.spec_for(
        "seg0/b0/moe/experts/gate", (24, 60, 2048, 1408), mesh
    )
    assert spec == P(None, None, "data", "model")


def test_vocab_sharding_falls_back_when_indivisible():
    mesh = FakeMesh(data=16, model=16)
    ok = DEFAULT_RULES.spec_for("embed/table", (129280, 7168), mesh)
    assert ok == P("model", "data")
    # 92553 is not divisible by 16 -> vocab replicated, d over data
    fallback = DEFAULT_RULES.spec_for("embed/table", (92553, 2048), mesh)
    assert fallback == P(None, "data")


def test_norms_replicated():
    mesh = FakeMesh(data=16, model=16)
    assert DEFAULT_RULES.spec_for("seg0/b0/norm1/scale", (24, 4096), mesh) == P()


def test_kv_heads_small_dims():
    mesh = FakeMesh(data=16, model=16)
    # MQA: kv proj output dim 1*128=128 divides 16 -> still column-sharded
    spec = DEFAULT_RULES.spec_for("seg0/b0/attn/wk/w", (88, 6144, 128), mesh)
    assert spec == P(None, "data", "model")


# ------------------------------------------------------------ MoE behaviour
def _moe_setup(e=8, k=2, d=32, f=16, shared=0):
    cfg = MoEConfig(
        d_model=d, d_ff=f, num_experts=e, top_k=k, num_shared=shared,
        compute_dtype=jnp.float32,
    )
    params = init_moe(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_moe_output_shape_and_finite(rng):
    cfg, params = _moe_setup(shared=1)
    x = jnp.asarray(rng.standard_normal((2, 64, 32)).astype(np.float32))
    out, aux = moe_apply(params, cfg, x)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all())
    assert float(aux["balance_loss"]) >= 0
    assert float(aux["z_loss"]) >= 0


def test_moe_capacity_drops_are_bounded(rng):
    """With capacity_factor >= 1 and perfectly uniform routing nothing
    drops; with adversarially-skewed routing outputs stay finite."""
    cfg, params = _moe_setup(e=4, k=1)
    x = jnp.asarray(np.tile(rng.standard_normal((1, 1, 32)), (1, 64, 1)).astype(np.float32))
    out, _ = moe_apply(params, cfg, x)  # identical tokens -> one expert hot
    assert bool(jnp.isfinite(out).all())


def test_moe_permutation_equivariance(rng):
    """Permuting tokens permutes outputs identically when capacity is
    large enough that nothing drops (dropping is slot-order-dependent by
    design — GShard locality semantics)."""
    cfg = MoEConfig(
        d_model=16, d_ff=8, num_experts=4, top_k=1,
        capacity_factor=4.0,  # no drops -> equivariance is exact
        compute_dtype=jnp.float32,
    )
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = rng.standard_normal((1, 16, 16)).astype(np.float32)
    out1, _ = moe_apply(params, cfg, jnp.asarray(x))
    perm = rng.permutation(16)
    out2, _ = moe_apply(params, cfg, jnp.asarray(x[:, perm]))
    np.testing.assert_allclose(
        np.asarray(out1)[:, perm], np.asarray(out2), rtol=1e-4, atol=1e-5
    )


@given(
    e=st.sampled_from([4, 8]),
    k=st.integers(1, 3),
    s=st.sampled_from([16, 32]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_property_moe_matches_dense_oracle(e, k, s, seed):
    """Sort-based dispatch == brute-force per-token expert loop (when no
    token exceeds capacity)."""
    rng = np.random.default_rng(seed)
    cfg = MoEConfig(
        d_model=16, d_ff=8, num_experts=e, top_k=k,
        capacity_factor=float(e),  # capacity >= all tokens: nothing drops
        compute_dtype=jnp.float32,
    )
    params = init_moe(jax.random.PRNGKey(seed % 1000), cfg)
    x = jnp.asarray(rng.standard_normal((1, s, 16)).astype(np.float32))
    got, _ = moe_apply(params, cfg, x)

    # oracle: dense routing
    from repro.models.common import linear

    logits = (x @ params["router"]["w"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    we = params["experts"]
    expect = np.zeros((1, s, 16), np.float32)
    for t in range(s):
        for j in range(k):
            eid = int(top_e[0, t, j])
            xin = np.asarray(x[0, t])
            g = xin @ np.asarray(we["gate"][eid])
            u = xin @ np.asarray(we["up"][eid])
            h = (g / (1 + np.exp(-g))) * u
            expect[0, t] += float(top_p[0, t, j]) * (h @ np.asarray(we["down"][eid]))
    np.testing.assert_allclose(np.asarray(got), expect, rtol=5e-3, atol=5e-4)
