"""Single-table statements keep their reference answers and work counts
to the bit.

``PINNED`` was taken from the reference as it stood before statements
could join tables or cut at a LIMIT: every statement of every mix, the
pipeline's SQL nodes included, at the tiny sizes of ``tiny.py`` and two
seeds.  For each statement it holds a SHA-256 of the reference's answer
(column names, dtypes and bytes, in order) and the ``(operations,
bytes)`` that the harness counts for it behind ``query_roofline``.
"""
import hashlib

import numpy as np
import pytest

import harness
import reference
import tiny

#: the cells whose mixes hold every single-table statement of the benchmark
CELLS = ["taxi.dashboard", "tpch_sf1.q1", "taxi.pipeline", "tpch_sf1.q6"]
SEEDS = [3, 2**31 + 11]
#: peaks under which the least time is the operations, or the bytes
COUNT_OPS = {"flops_per_s": 1.0, "hbm_bytes_per_s": float("inf")}
COUNT_BYTES = {"flops_per_s": float("inf"), "hbm_bytes_per_s": 1.0}

PINNED = {
    ('taxi.dashboard', 3): {
        'week_pickups[0]': ('3e77f520a96dc380', 28000.0, 58120.0),
        'week_pickups[1]': ('56c46077f3856bbe', 28000.0, 58120.0),
        'week_pickups[2]': ('849969116674aa62', 28000.0, 58120.0),
        'week_pickups[3]': ('061fd808dd106655', 28000.0, 58120.0),
        'week_pickups[4]': ('bd5847378e7bd0f9', 28000.0, 58120.0),
        'week_pickups[5]': ('ecb7da1ba26c8546', 28000.0, 58120.0),
        'week_pickups[6]': ('5b5687d502695486', 28000.0, 58120.0),
        'week_pickups[7]': ('83d6e3344263135a', 28000.0, 58120.0),
        'week_pickups[8]': ('6f4d027279cdcfdc', 28000.0, 58120.0),
        'week_pickups[9]': ('193a97459d985b73', 28000.0, 58120.0),
        'week_pickups[10]': ('20da005da0012f83', 28000.0, 58120.0),
        'week_pickups[11]': ('7a59c89eb98f75af', 28000.0, 58120.0),
        'window_pickups[None]': ('629a94e3d69cf379', 360000.0, 722120.0),
        'window_dropoffs[None]': ('dff25fe484f06097', 360000.0, 722120.0),
    },
    ('taxi.dashboard', 2147483659): {
        'week_pickups[0]': ('4ffab569e39ec6f4', 28000.0, 58120.0),
        'week_pickups[1]': ('f5b61de30d4d2723', 28000.0, 58120.0),
        'week_pickups[2]': ('a56abd5f5cb9e16b', 28000.0, 58120.0),
        'week_pickups[3]': ('ee79dcf49af76c72', 28000.0, 58120.0),
        'week_pickups[4]': ('325c0548014f389f', 28000.0, 58120.0),
        'week_pickups[5]': ('606e1c764c018bc0', 28000.0, 58120.0),
        'week_pickups[6]': ('c62aec5f46c355f3', 28000.0, 58120.0),
        'week_pickups[7]': ('df416736b2195b72', 28000.0, 58120.0),
        'week_pickups[8]': ('20ec62e745ce9d34', 28000.0, 58120.0),
        'week_pickups[9]': ('375d4586018b90a0', 28000.0, 58120.0),
        'week_pickups[10]': ('4392095ac5f30f34', 28000.0, 58120.0),
        'week_pickups[11]': ('b8cd3e13e68efbd3', 28000.0, 58120.0),
        'window_pickups[None]': ('dfdc567ac656d7bb', 360000.0, 722120.0),
        'window_dropoffs[None]': ('c2580c4b56a2f007', 360000.0, 722120.0),
    },
    ('tpch_sf1.q1', 3): {
        'q1[None]': ('fa295d7ce9c8564f', 946688.0, 1065160.0),
    },
    ('tpch_sf1.q1', 2147483659): {
        'q1[None]': ('1f8759b7a038cfe6', 946688.0, 1065160.0),
    },
    ('taxi.pipeline', 3): {
        'trips': ('fd17eca23ffba630', 0.0, 720000.0),
        'pickups': ('9f93679d5f1abd34', 180000.0, 756516.0),
    },
    ('taxi.pipeline', 2147483659): {
        'trips': ('ffba25f4fbdb913f', 0.0, 720000.0),
        'pickups': ('c0596c8db771e02c', 180000.0, 755832.0),
    },
    ('tpch_sf1.q6', 3): {
        'q6[None]': ('269f5b1ba4c77f6b', 2350.0, 9404.0),
    },
    ('tpch_sf1.q6', 2147483659): {
        'q6[None]': ('41af0f5d5597617b', 2350.0, 9404.0),
    },
}


def digest(out) -> str:
    h = hashlib.sha256()
    for name, column in out.items():
        a = np.ascontiguousarray(column)
        h.update(f"{name}:{a.dtype.str}:{len(a)};".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _work(cell, traffic, key, tables, answer):
    """``(operations, bytes)`` of one statement, as the harness counts them."""
    request = harness.Request(key[0], key[1], traffic.op(key[0]), 0.0, 0.0, answer)
    want = {key: answer}
    return tuple(harness._least_times(cell, traffic, [request], tables, want, peak)[0]
                 for peak in (COUNT_OPS, COUNT_BYTES))


def statements(name, seed):
    """``label -> (digest, operations, bytes)`` of every statement of a cell's mix."""
    cell = tiny.cell(name)
    tables = cell.generate(seed)
    mix = cell.mix
    got = {}
    if mix["kind"] == "query":
        traffic = harness.Traffic(mix, seed)
        for r, p in traffic.every():
            answer = reference.run_statement(traffic.statement(r), tables, traffic.params(r, p))
            got[f"{traffic.name(r)}[{p}]"] = (
                digest(answer),) + _work(cell, traffic, (r, p), tables, answer)
        return got
    env = dict(tables)
    for node in mix["pipeline"]["nodes"]:
        if node["kind"] != "sql":
            continue
        answer = reference.run_statement(node["statement"], env)
        one = harness.Traffic({"requests": [{"name": node["name"], "weight": 1, "sql": "",
                                             "statement": node["statement"]}]}, seed)
        got[node["name"]] = (digest(answer),) + _work(cell, one, (0, None), env, answer)
        env[node["name"]] = answer
    return got


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CELLS)
def test_answers_and_work_match_the_pinned_digests(name, seed):
    assert statements(name, seed) == PINNED[(name, seed)]


if __name__ == "__main__":  # prints PINNED afresh
    print("PINNED = {")
    for name in CELLS:
        for seed in SEEDS:
            print(f"    ({name!r}, {seed}): {{")
            for label, value in statements(name, seed).items():
                print(f"        {label!r}: {value!r},")
            print("    },")
    print("}")
