"""Every mix through ``repro.Client`` at a tiny size, Pallas interpreted,
every answer checked against the numpy reference."""
import numpy as np
import pytest

import harness
import reference
import tiny


@pytest.mark.parametrize("name", tiny.CELLS)
def test_every_answer_matches_the_reference(name):
    result = tiny.run(name)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compiles_in_window"] == 0
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    assert list(result)[-2:] == ["compared", "compiles_in_window"]


@pytest.mark.parametrize("name, path", [("taxi.dashboard", "kernel"),
                                        ("tpch_sf1.q1", "jnp"),
                                        ("tpch_sf1.q6", "jnp")])
def test_statements_take_the_route_the_cell_names(name, path, tmp_path):
    import repro

    cell = tiny.cell(name)
    traffic = harness.Traffic(cell.mix, 1)
    with repro.Client(tmp_path / "lake") as client:
        for table, data in cell.generate(1).items():
            client.write_table(table, data, schema=repro.Schema.of(
                **cell.config["tables"][table]["columns"]))
        for r, p in traffic.every():
            assert client.explain(traffic.sql(r, p)).engine_path == path


def test_reference_answers_a_known_table():
    tables = {"t": {"k": np.array([2, 1, 2, 2], np.int32),
                    "v": np.array([1.5, 2.0, 0.5, 1.0], np.float32),
                    "d": np.array([0, 1, 2, 3], np.int32)}}
    stmt = {"table": "t", "where": [["d", ">=", 1]], "group_by": ["k"],
            "aggs": [{"name": "n", "fn": "count"},
                     {"name": "s", "fn": "sum", "expr": "v * 2"},
                     {"name": "a", "fn": "mean", "expr": "v"}],
            "order_by": [["n", "desc"]]}
    out = reference.run_statement(stmt, tables)
    assert out["k"].tolist() == [2, 1]
    assert out["n"].tolist() == [2, 1]
    assert out["s"].tolist() == [3.0, 4.0]
    assert out["a"].tolist() == [0.75, 2.0]
    assert reference.compare(out, out, stmt) == (0, 0.0)
    swapped = {c: v[::-1] for c, v in out.items()}
    assert reference.compare(swapped, out, stmt)[0] == 1  # ORDER BY broken
    off = dict(out, s=out["s"] * np.array([1.01, 1.0]))  # a float cell 1% off
    assert reference.compare(off, out, stmt) == (0, pytest.approx(0.01))
