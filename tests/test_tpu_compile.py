"""Compile the Pallas kernels for a described TPU v5e, at real widths.

Nothing runs: XLA and Mosaic compile for a chip that is described, not
attached, so tiling, lowering and VMEM limits are checked without one.
Each compiled program must carry the Mosaic kernel (``tpu_custom_call``).
The topology is described inside a fixture, never at import: only the
process that runs these tests may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine import compile_query, parse_sql
from repro.engine.columnar import Columnar
from repro.engine.route import plan_route
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.runtime import device

ROWS = 16_000_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Shapes on one described chip, with the kernels compiled by Mosaic.

    The backend JAX runs on here is the CPU, so the device module would
    interpret the kernels; the test steers it to the TPU answer.  The
    persistent cache is off meanwhile: an entry compiled for a described
    chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    sharding = SingleDeviceSharding(topo.devices[0])
    cache_was_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(device, "pallas_interpret", lambda: False)
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        jax.clear_caches()
        try:
            yield lambda shape, dtype: jax.ShapeDtypeStruct(
                shape, dtype, sharding=sharding
            )
        finally:
            jax.clear_caches()
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("num_groups", [64, 1024])
@pytest.mark.parametrize(
    "where,native",
    [("f >= 10", True), ("f >= 10 OR v < 3", False)],
    ids=["native-filter", "mask-input"],
)
def test_fused_filter_agg_query_compiles(one_chip, num_groups, where, native):
    """The kernel route of a whole query program, at 16M rows."""
    query = parse_sql(
        f"SELECT k, SUM(v) AS s, AVG(v) AS a, COUNT(*) AS n FROM t "
        f"WHERE {where} GROUP BY k"
    )
    route = plan_route(
        query, engine="kernel",
        stats={"k": (0, num_groups - 1), "v": (0, 100), "f": (0, 100)},
    )
    assert route.engine_path == "kernel" and route.native_filter is native
    i32 = one_chip((ROWS,), jnp.int32)
    rel = Columnar({"k": i32, "v": i32, "f": i32}, one_chip((ROWS,), jnp.bool_))
    compiled = compile_query(query, route=route).lower(rel).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_flash_attention_compiles(one_chip):
    q = one_chip((1, 32, 4096, 128), jnp.bfloat16)
    kv = one_chip((1, 8, 4096, 128), jnp.bfloat16)
    _compiled_text(flash_attention, q, kv, kv)


def test_decode_attention_compiles(one_chip):
    q = one_chip((8, 32, 128), jnp.bfloat16)
    kv = one_chip((8, 8, 4096, 128), jnp.bfloat16)
    lengths = one_chip((8,), jnp.int32)
    _compiled_text(decode_attention, q, kv, kv, lengths)
