"""The generators fix what each statement's scan hands to the device:
two seeds give every statement of every mix the same row count."""
import json

import numpy as np
import pytest

import harness
import reference
import tiny


def _counts(name, seed):
    cell = tiny.cell(name)
    tables = cell.generate(seed)
    mix = cell.mix
    if mix["kind"] == "query":
        traffic = harness.Traffic(mix, seed)
        return [reference.selected_rows(traffic.statement(r), tables, traffic.params(r, p))
                for r, p in traffic.every()]
    return [reference.selected_rows(n["statement"], tables)
            for n in mix["pipeline"]["nodes"]
            if n["kind"] == "sql" and n["statement"]["table"] in tables]


@pytest.mark.parametrize("name", tiny.CELLS)
def test_two_seeds_hand_over_the_same_rows(name):
    assert _counts(name, 3) == _counts(name, 2**31 + 11)


def test_a_fixed_order_is_rotated_by_the_seed():
    mix = tiny.cell("taxi.dashboard").mix
    assert "order_seed" in mix
    a = harness.Traffic(mix, 3).plan(306)
    b = harness.Traffic(mix, 2**31 + 11).plan(306)
    ops_a = [r for r, _ in a]
    ops_b = [r for r, _ in b]
    assert ops_a != ops_b
    assert any(ops_b == ops_a[k:] + ops_a[:k] for k in range(len(ops_a)))
    assert a != [(r, p) for r, p in b]
    # without an order_seed, the first requests of the seed's stream
    shuffled = {k: v for k, v in mix.items() if k != "order_seed"}
    stream = harness.Traffic(shuffled, 3).stream()
    assert harness.Traffic(shuffled, 3).plan(40) == [next(stream) for _ in range(40)]


def test_seeds_change_the_data():
    a = tiny.cell("taxi.dashboard").generate(3)["taxi_table"]
    b = tiny.cell("taxi.dashboard").generate(4)["taxi_table"]
    assert np.array_equal(a["pickup_at"], b["pickup_at"])
    assert not np.array_equal(a["pickup_location_id"], b["pickup_location_id"])
    x = tiny.cell("tpch_sf1.q1").generate(3)["lineitem"]
    y = tiny.cell("tpch_sf1.q1").generate(4)["lineitem"]
    assert not np.array_equal(x["l_shipdate"], y["l_shipdate"])
    assert np.array_equal(np.sort(x["l_shipdate"]), np.sort(y["l_shipdate"]))


def test_taxi_days_hold_fixed_counts():
    taxi = harness.load_module(harness.BENCH / "configs" / "taxi_2019.py")
    counts = taxi.day_counts(16_000_000, 90)
    assert counts.sum() == 16_000_000
    assert set(counts.tolist()) == {177_777, 177_778}


@pytest.mark.parametrize("name", ["taxi.dashboard", "tpch_sf1.q1"])
def test_tables_carry_every_column_of_the_record(name):
    cell = tiny.cell(name)
    for table, data in cell.generate(5).items():
        columns = cell.config["tables"][table]["columns"]
        assert list(data) == list(columns)
        assert {c: str(a.dtype) for c, a in data.items()} == columns
        assert len({len(a) for a in data.values()}) == 1


def test_lineitem_keys_follow_dbgen():
    tpch = harness.load_module(harness.BENCH / "configs" / "tpch_sf1.py")
    orderkey, linenumber = tpch.orders(np.random.default_rng(0), 10_000)
    assert len(orderkey) == 10_000 and linenumber.min() == 1 and linenumber.max() <= 7
    assert np.all(np.diff(orderkey) >= 0) and set(np.unique(orderkey) % 32) <= set(range(1, 9))
    starts = np.flatnonzero(np.diff(orderkey)) + 1
    assert np.all(linenumber[starts] == 1)


def test_a_configuration_states_its_own_tiny_sizes(tmp_path, monkeypatch):
    config = tmp_path / "tpch_q3.json"
    config.write_text(json.dumps({"scale_factor": 1, "tiny": {"scale_factor": 0.01}}))
    spec = dict(tiny.SPEC, configs=tiny.SPEC["configs"] + [{"name": "tpch_q3",
                                                             "file": str(config)}])
    monkeypatch.setattr(tiny, "SPEC", spec)
    assert tiny.sizes("tpch_q3") == {"scale_factor": 0.01}
    assert tiny.sizes("tpch_sf1") == {"rows": 60_000}
    assert tiny.CELLS == [w["name"] for w in spec["workloads"]]
