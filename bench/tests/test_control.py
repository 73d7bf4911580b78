"""The control, the reference one precision below the configuration's,
comes out not correct in every cell; the exact reference in the
program's place comes out correct."""
import pytest

import control
import harness
import tiny


@pytest.mark.parametrize("name", tiny.CELLS)
@pytest.mark.parametrize("seed", [5, 2**31 + 3])
def test_the_control_fails_a_number(name, seed):
    cell = tiny.cell(name)
    numbers = control.control_numbers(cell, seed)
    limits = cell.mix["limits"]
    assert any(numbers[k] > limits[k] for k in numbers), numbers


@pytest.mark.parametrize("name", ["taxi.dashboard", "tpch_sf1.q1"])
def test_the_reference_in_the_programs_place_passes(name):
    cell = tiny.cell(name)
    tables = cell.generate(9)
    traffic = harness.Traffic(cell.mix, 9)
    keys = traffic.every()
    want = harness.query_answers(traffic, keys, tables)
    requests = [harness.Request(r, p, traffic.op(r), 0.0, 0.0, want[(r, p)]) for r, p in keys]
    numbers, failed = harness.judge_queries(cell.mix, traffic, requests, 0, want)
    assert failed == 0 and all(v == 0 for v in numbers.values())


@pytest.mark.parametrize("name", ["tpch_sf1.q1", "tpch_sf1.q6"])
def test_the_control_fails_the_float_comparison(name):
    cell = tiny.cell(name)
    numbers = control.control_numbers(cell, 5)
    assert numbers["float_rel_err"] > 10 * cell.mix["limits"]["float_rel_err"], numbers
