"""Phase timing of queries, stages and executor attempts.

The contract under test:

* ``QueryExecuted``'s finer phases sit inside the coarse ones: read
  inside scan, device and fetch inside exec, and the coarse phases inside
  the wall clock;
* every query has a process-unique ``query_id`` that its shard reads
  carry, and counts the programs it handed to XLA;
* a stage's phases account for the stage's span;
* the executor's ``duration_s`` waits for the device and leaves the
  compile out;
* while the profiler traces, the phases are ``repro.*`` spans on the host
  plane, and compiled programs are named by what they compute.
"""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Client
from repro.core import Pipeline
from repro.engine.exec import compile_query, program_name
from repro.engine.sql import parse_sql
from repro.examples_data import TAXI_SCHEMA, make_taxi_data
from repro.runtime import ExecutorConfig, FunctionSpec, ServerlessExecutor
from repro.runtime.warm import WarmFunctionCache
from repro.telemetry.tracing import STAGE_PHASES

QUERY_PHASES = ("parse_s", "plan_s", "scan_s", "exec_s",
                "read_s", "copy_s", "device_s", "fetch_s")
QUERIES = (
    "SELECT COUNT(*) AS n FROM taxi_table",
    "SELECT pickup_location_id, COUNT(*) AS n FROM taxi_table "
    "WHERE pickup_at >= '2019-04-01' GROUP BY pickup_location_id",
    "SELECT passenger_count, SUM(dropoff_location_id) AS d FROM taxi_table "
    "GROUP BY passenger_count ORDER BY d DESC",
)


def _client(shard_rows: int = 512) -> Client:
    return Client.ephemeral(
        shard_rows=shard_rows, executor_config=ExecutorConfig(max_workers=4)
    )


def _write_taxi(client: Client, rows: int = 3000, seed: int = 7) -> None:
    client.write_table(
        "taxi_table", make_taxi_data(rows, np.random.default_rng(seed)),
        schema=TAXI_SCHEMA,
    )


def _query_events(client: Client, statements) -> list:
    sub = client.events(follow=True)
    for sql in statements:
        client.query(sql)
    events = sub.poll()
    sub.close()
    return events


def _pipeline() -> Pipeline:
    p = Pipeline("phases")
    p.sql(
        "trips",
        "SELECT pickup_location_id, passenger_count AS count FROM taxi_table"
        " WHERE pickup_at >= '2019-04-01'",
    )
    p.sql(
        "pickups",
        "SELECT pickup_location_id, COUNT(*) AS counts FROM trips"
        " GROUP BY pickup_location_id ORDER BY counts DESC",
    )
    return p


# ------------------------------------------------------------ query phases
def test_query_phases_nest_inside_the_coarse_ones():
    with _client() as client:
        _write_taxi(client)
        events = _query_events(client, QUERIES * 2)
    queries = [e for e in events if e.kind == "QueryExecuted"]
    assert len(queries) == 2 * len(QUERIES)
    for q in queries:
        assert all(getattr(q, f) >= 0.0 for f in QUERY_PHASES)
        assert q.read_s <= q.scan_s
        assert q.device_s + q.fetch_s <= q.exec_s
        assert q.parse_s + q.plan_s + q.scan_s + q.exec_s <= q.wall_s
        # the copy is enqueued in the scan and awaited in the execution
        assert q.read_s + q.copy_s + q.device_s + q.fetch_s <= q.scan_s + q.exec_s


def test_query_ids_are_unique_and_tag_their_shard_reads():
    with _client() as client:
        _write_taxi(client)
        events = _query_events(client, QUERIES)
    queries = [e for e in events if e.kind == "QueryExecuted"]
    ids = [q.query_id for q in queries]
    assert len(set(ids)) == len(ids) and all(i > 0 for i in ids)
    reads = [e for e in events if e.kind == "ScanShardRead"]
    for q in queries:
        mine = [r for r in reads if r.query_id == q.query_id]
        assert len(mine) == q.shards_read > 0
    assert {r.query_id for r in reads} == set(ids)


def test_a_new_row_count_compiles_and_its_repeat_does_not():
    sql = "SELECT passenger_count, SUM(pickup_location_id) AS f FROM taxi_table GROUP BY passenger_count"
    with _client() as client:
        # a row count no other test of this file scans
        _write_taxi(client, rows=3331)
        first, repeat = [e for e in _query_events(client, [sql, sql])
                         if e.kind == "QueryExecuted"]
    assert first.compiles >= 1
    assert repeat.compiles == 0


# ------------------------------------------------------------ stage phases
def test_stage_phases_account_for_the_stage():
    # rows enough that the phases outweigh the stage's bookkeeping
    with _client(shard_rows=8192) as client:
        _write_taxi(client, rows=400_000)
        for _ in range(2):  # cold (compiling), then warm
            handle = client.run(_pipeline(), cache=False).raise_for_state()
            events = client.runlog.get(handle.run_id)
            finished = [e for e in events if e.kind == "StageFinished"]
            assert finished
            for e in finished:
                total = sum(getattr(e, f"{p}_s") for p in STAGE_PHASES)
                assert all(getattr(e, f"{p}_s") >= 0.0 for p in STAGE_PHASES)
                assert 0.9 * e.exec_s <= total <= e.exec_s
        assert sum(e.compiles for e in finished) == 0  # the warm run
        trace = handle.trace()
    for sid, spans in trace.stage_spans.items():
        ex = spans["exec"]
        phases = [c for c in ex.children if c.kind == "phase"]
        assert [c.attrs["phase"] for c in phases] == [
            p for p in STAGE_PHASES if any(c.attrs["phase"] == p for c in phases)
        ]
        assert {"read", "device", "write"} <= {c.attrs["phase"] for c in phases}
        for c in phases:
            assert ex.start <= c.start <= c.end <= ex.end
    assert "device_ms" in trace.describe()
    chrome = {e["name"] for e in trace.to_chrome_trace()["traceEvents"] if e["ph"] == "X"}
    assert {f"{p} stage 0" for p in ("read", "device", "write")} <= chrome
    assert trace.coverage() >= 0.95


# --------------------------------------------------------------- executor
class _SlowCompile(WarmFunctionCache):
    """A warm cache whose cold compile takes at least ``COMPILE_S``."""

    COMPILE_S = 1.5

    def get_or_compile(self, spec, *example_inputs):
        if not self.has_fingerprint(spec.fingerprint):
            time.sleep(self.COMPILE_S)
        return super().get_or_compile(spec, *example_inputs)


def _heavy(x):
    """Enough work that its call returns long before its outputs are ready."""
    for _ in range(8):
        x = jnp.tanh(x @ x)
    return x


def test_executor_duration_waits_for_the_device_without_the_compile():
    x = jnp.ones((1024, 1024)) / 1024
    spec = FunctionSpec(name="heavy", fn=_heavy)
    with ServerlessExecutor(ExecutorConfig(max_workers=2),
                            warm_cache=_SlowCompile()) as ex:
        _, cold = ex.run_recorded(spec, x)
        _, warm = ex.run_recorded(spec, x)
        history = ex.latency_history()[spec.fingerprint]
        compiled = ex.warm_cache.get_or_compile(spec, x)
    waits = []
    for _ in range(3):
        y = compiled(x)
        t0 = time.perf_counter()
        jax.block_until_ready(y)
        waits.append(time.perf_counter() - t0)
    assert cold.compile_s >= _SlowCompile.COMPILE_S and cold.compiles >= 1
    assert warm.compile_s < _SlowCompile.COMPILE_S and warm.compiles == 0
    for record in (cold, warm):
        assert record.duration_s >= 0.5 * min(waits)  # the wait is in it
        assert record.duration_s < _SlowCompile.COMPILE_S  # the compile is not
    assert history == [cold.duration_s, warm.duration_s]


# ----------------------------------------------------- profiler and names
def _host_span_names(trace_dir) -> set:
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    return {
        ev.name
        for plane in data.planes if plane.name == "/host:CPU"
        for line in plane.lines
        for ev in line.events
    }


def test_query_and_stage_leave_repro_spans_on_the_host_plane(tmp_path):
    with _client() as client:
        _write_taxi(client)
        client.query(QUERIES[1])  # compiled before the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            client.query(QUERIES[1])
            client.run(_pipeline(), cache=False).raise_for_state()
        finally:
            jax.profiler.stop_trace()
    names = _host_span_names(tmp_path)
    for phase in ("parse", "plan", "read", "copy", "device", "fetch"):
        assert f"repro.query.{phase}" in names
    for phase in STAGE_PHASES + ("commit",):
        assert f"repro.stage.{phase}" in names
    assert {"repro.query", "repro.stage"} <= names


def test_statements_differing_only_in_literals_share_a_program_name():
    a = parse_sql("SELECT k, SUM(v) AS s FROM t WHERE v > 3 GROUP BY k")
    b = parse_sql("SELECT k, SUM(v) AS s FROM t WHERE v > 5 GROUP BY k")
    c = parse_sql("SELECT k, COUNT(*) AS n FROM t WHERE v > 3 GROUP BY k")
    assert program_name(a) == program_name(b) == "t_by_k_sum_where_v"
    assert program_name(c) != program_name(a)
    fa, fb = compile_query(a), compile_query(b)
    assert fa is not fb and fa.__name__ == fb.__name__ == "t_by_k_sum_where_v"
    from repro.engine.columnar import Columnar

    rel = Columnar.from_numpy({"k": np.arange(4, dtype=np.int32),
                               "v": np.arange(4, dtype=np.float32)})
    assert "jit_t_by_k_sum_where_v" in fa.lower(rel).as_text()


def test_stage_programs_carry_the_stage_name():
    cache = WarmFunctionCache()
    spec = FunctionSpec(name="taxi_demo/stage0", fn=lambda x: x + 1)
    compiled = cache.get_or_compile(spec, jnp.ones(4))
    assert "jit_taxi_demo_stage0" in compiled.as_text()
