"""The reference on hand-made tables: joins that keep every match, WHERE
on each joined table, ORDER BY ... DESC with LIMIT, and the comparison
of an answer cut at a LIMIT."""
import numpy as np
import pytest

import reference

i32 = np.int32


def _tables():
    """Facts with a many-to-one key into ``dim``, and a many-to-many key
    (``f_tag`` against ``tag_k``, each value held by several rows)."""
    return {
        "facts": {"f_id": np.arange(8, dtype=i32),
                  "f_dim": np.array([1, 2, 1, 3, 2, 1, 4, 2], i32),
                  "f_tag": np.array([7, 7, 8, 9, 8, 7, 9, 9], i32),
                  "f_val": np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0], np.float32)},
        "dim": {"d_id": np.array([1, 2, 3, 5], i32),
                "d_seg": np.array([0, 1, 1, 0], np.int8)},
        "tags": {"tag_k": np.array([7, 7, 8, 7, 9], i32),
                 "tag_w": np.array([10, 20, 30, 40, 50], i32)},
    }


def test_a_many_to_one_join_takes_each_facts_dimension_row():
    stmt = {"table": "facts", "joins": [{"table": "dim", "on": ["f_dim", "d_id"]}],
            "group_by": ["d_seg"],
            "aggs": [{"name": "n", "fn": "count"}, {"name": "s", "fn": "sum", "expr": "f_val"}]}
    out = reference.run_statement(stmt, _tables())
    # f_dim 4 has no dimension row and drops; segment 0 is d_id 1, 1 is 2 and 3
    assert out["d_seg"].tolist() == [0, 1]
    assert out["n"].tolist() == [3, 4]
    assert out["s"].tolist() == [1 + 3 + 6, 2 + 4 + 5 + 8]
    assert reference.selected_rows(stmt, _tables()) == 7


def test_a_many_to_many_join_returns_every_match():
    stmt = {"table": "facts", "joins": [{"table": "tags", "on": ["f_tag", "tag_k"]}],
            "group_by": ["f_tag"],
            "aggs": [{"name": "n", "fn": "count"}, {"name": "w", "fn": "sum", "expr": "tag_w"}]}
    out = reference.run_statement(stmt, _tables())
    # tag 7: 3 facts x 3 tags; tag 8: 2 facts x 1 tag; tag 9: 3 facts x 1 tag
    assert out["f_tag"].tolist() == [7, 8, 9]
    assert out["n"].tolist() == [9, 2, 3]
    assert out["w"].tolist() == [3 * (10 + 20 + 40), 2 * 30, 3 * 50]
    assert reference.selected_rows(stmt, _tables()) == 14


def test_a_first_match_answer_to_a_many_to_many_join_is_wrong():
    tables = _tables()
    stmt = {"table": "facts", "joins": [{"table": "tags", "on": ["f_tag", "tag_k"]}],
            "group_by": ["f_tag"],
            "aggs": [{"name": "n", "fn": "count"}, {"name": "w", "fn": "sum", "expr": "tag_w"}]}
    want = reference.run_statement(stmt, tables)
    # each fact joined to the first tag row of its key only
    first = {int(k): int(w) for k, w in zip(tables["tags"]["tag_k"][::-1],
                                            tables["tags"]["tag_w"][::-1])}
    keys = tables["facts"]["f_tag"]
    got = {"f_tag": np.array([7, 8, 9], i32),
           "n": np.array([np.sum(keys == k) for k in (7, 8, 9)]),
           "w": np.array([np.sum(keys == k) * first[k] for k in (7, 8, 9)])}
    assert got["n"].tolist() == [3, 2, 3]
    wrong, _ = reference.compare(got, want, stmt)
    assert wrong == 2  # tag 7's count and its sum


def test_where_applies_to_the_table_that_holds_each_column():
    stmt = {"table": "facts",
            "joins": [{"table": "dim", "on": ["f_dim", "d_id"]},
                      {"table": "tags", "on": ["f_tag", "tag_k"]}],
            "where": [["d_seg", "=", 1], ["f_val", ">", 2.0], ["tag_w", "<", 50]],
            "group_by": ["f_id"], "aggs": [{"name": "n", "fn": "count"}]}
    tables = _tables()
    assert reference.table_rows(stmt, tables) == {"facts": 6, "dim": 2, "tags": 4}
    out = reference.run_statement(stmt, tables)
    # facts with f_val > 2 in segment 1 (d_id 2, 3): ids 3, 4, 7 (tags 9, 8, 9);
    # tag 9's one row has tag_w 50 and is cut, tag 8 keeps its one row
    assert out["f_id"].tolist() == [4]
    assert out["n"].tolist() == [1]
    assert reference.selected_rows(stmt, tables) == 1


def test_an_ambiguous_column_is_an_error():
    tables = _tables()
    tables["dim"]["f_val"] = np.zeros(4, np.float32)
    stmt = {"table": "facts", "joins": [{"table": "dim", "on": ["f_dim", "d_id"]}],
            "group_by": ["d_seg"], "aggs": [{"name": "s", "fn": "sum", "expr": "f_val"}]}
    with pytest.raises(ValueError, match="ambiguous"):
        reference.run_statement(stmt, tables)


def test_a_join_names_the_joined_tables_column_second():
    stmt = {"table": "facts", "joins": [{"table": "dim", "on": ["d_id", "f_dim"]}],
            "group_by": ["d_seg"], "aggs": [{"name": "n", "fn": "count"}]}
    with pytest.raises(ValueError, match="first column"):
        reference.run_statement(stmt, _tables())


LIMITED = {"table": "facts", "group_by": ["f_id"],
           "aggs": [{"name": "s", "fn": "sum", "expr": "f_val"}],
           "order_by": [["s", "desc"], ["f_id", "asc"]], "limit": 3}


def test_order_by_desc_then_limit():
    want = reference.run_statement(LIMITED, _tables())
    assert want["f_id"].tolist() == [7, 6, 5, 4, 3, 2, 1, 0]  # the whole ordered answer
    got = reference.head(want, LIMITED)
    assert got["f_id"].tolist() == [7, 6, 5] and got["s"].tolist() == [8.0, 7.0, 6.0]
    assert reference.kept_rows(LIMITED, 8) == 3 and reference.kept_rows(LIMITED, 2) == 2
    assert reference.compare(got, want, LIMITED) == (0, 0.0)


def _near_tie():
    """An answer whose 3rd and 4th rows differ by one part in 10**4."""
    tables = _tables()
    tables["facts"]["f_val"] = np.array([1, 2, 3, 4, 5.9994, 6, 7, 8], np.float32)
    want = reference.run_statement(LIMITED, tables)
    assert want["f_id"].tolist()[:4] == [7, 6, 5, 4]
    return want


def _rows(want, ids):
    at = [want["f_id"].tolist().index(i) for i in ids]
    return {c: v[at] for c, v in want.items()}


def test_a_near_tie_at_the_cut_may_swap_within_the_float_limit():
    want = _near_tie()
    swapped = _rows(want, [7, 6, 4])  # the 4th row in place of the 3rd
    assert reference.compare(swapped, want, LIMITED, tie_rel_err=1e-3) == (0, 0.0)
    # the same swap where the mix allows less than the gap
    assert reference.compare(swapped, want, LIMITED, tie_rel_err=1e-5)[0] == 2


def test_a_row_from_far_beyond_the_cut_is_wrong():
    want = _near_tie()
    far = _rows(want, [7, 6, 0])  # the last row of the whole answer
    assert reference.compare(far, want, LIMITED, tie_rel_err=1e-3)[0] == 2


def test_a_kept_row_dropped_far_from_the_cut_is_wrong():
    want = _near_tie()
    dropped = _rows(want, [7, 5, 4])  # the 2nd row left out, the near 4th let in
    assert reference.compare(dropped, want, LIMITED, tie_rel_err=1e-3)[0] == 1


def test_a_row_not_in_the_answer_is_wrong():
    want = _near_tie()
    got = _rows(want, [7, 6, 5])
    got["f_id"] = np.array([7, 6, 99], got["f_id"].dtype)
    assert reference.compare(got, want, LIMITED, tie_rel_err=1e-3)[0] == 2


@pytest.mark.parametrize("ids", [[7, 6], [7, 6, 5, 4], []])
def test_a_wrong_row_count_is_wrong(ids):
    want = _near_tie()
    assert reference.compare(_rows(want, ids), want, LIMITED, tie_rel_err=1e-3) == (3, 0.0)
