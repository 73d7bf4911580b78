"""repro.telemetry — event bus, run tracing, metrics, runlog GC, CLI.

The observability contract under test:

* the **event set** of a run is a function of the pipeline + data, not of
  the parallelism knob — runs at parallelism 1/2/8 emit the same multiset
  of events once timestamps/sequence numbers/durations are stripped;
* **spans nest**: every span sits inside the run span, scan/node spans
  inside their stage's exec window, and queue hands off exactly where
  exec picks up;
* a mid-DAG **audit failure still closes the run span** — RunFinished is
  emitted with the failure state and the trace is persisted;
* warm runs surface as **rehydrate spans** and the trace accounts for
  ≥95% of wall-clock;
* **runlog traces are GC roots only within the TTL** — expired traces
  lose ref and blob in one pass, live traces keep their bytes pinned.
"""
import json

import numpy as np
import pytest

from repro.api import Client, RunState
from repro.cli import main
from repro.core import Pipeline
from repro.examples_data import TAXI_SCHEMA, make_taxi_data
from repro.runtime import ExecutorConfig
from repro.telemetry import (
    EVENT_TYPES,
    EventBus,
    MetricsRegistry,
    RunFinished,
    ScanShardRead,
    StageQueued,
    event_from_json_dict,
    read_spool,
)

N_ROWS = 2_000
PARALLELISMS = (1, 2, 8)

#: wall-clock fields stripped before comparing event sets across
#: parallelism levels (everything timing-dependent, nothing semantic).
#: StageScheduled's admission_wait_s/admission/warm describe scheduler
#: state at dispatch time (how long the gate held the stage, whether a
#: compiled executable already existed) — concurrency-dependent by
#: nature; its cost-model fields (est_cost_s, cp_rank, schedule,
#: streaming) stay under the invariance contract.  StageFinished's
#: phases are durations too, and its ``compiles`` depends on which
#: programs earlier runs in the same process already compiled.
_TIMING_FIELDS = {
    "ts", "seq", "wall_s", "exec_s", "commit_s", "dur_s",
    "baseline_s", "deadline_s",
    "admission_wait_s", "admission", "warm",
    "read_s", "copy_s", "compile_s", "device_s", "fetch_s", "write_s",
    "compiles",
}
#: timer-driven events — whether a straggler deadline fires depends on
#: scheduling noise, so they are excluded from the determinism contract
_TIMER_KINDS = {"SpeculationArmed", "SpeculationFired", "SpeculationWon"}


def _client(parallelism: int = 4) -> Client:
    return Client.ephemeral(
        shard_rows=512,
        executor_config=ExecutorConfig(
            max_workers=8, max_concurrent_stages=parallelism
        ),
    )


def build_fanout_pipeline(threshold: float = 10.0) -> Pipeline:
    """source -> (m0, m1) -> combine plus an audit: enough structure for
    real queue/exec overlap and a two-parent dependency edge."""
    p = Pipeline("telemetry_parity")
    p.sql(
        "trips",
        "SELECT pickup_location_id, passenger_count as count FROM taxi_table"
        " WHERE pickup_at >= '2019-04-01'",
    )

    @p.python
    def trips_expectation(ctx, trips):
        return trips.mean("count") > threshold

    for i in range(2):

        def make_model(i):
            def fn(ctx, trips):
                import jax.numpy as jnp

                col = trips.column("count").astype(jnp.float32)
                return {"stat": jnp.sort(col) * (i + 1)}

            fn.__name__ = f"m{i}"
            return fn

        p.python(make_model(i))

    @p.python
    def combine(ctx, m0, m1):
        import jax.numpy as jnp

        return {"delta": m1.column("stat") - m0.column("stat")}

    return p


def _write_taxi(client: Client, seed: int = 7) -> None:
    rng = np.random.default_rng(seed)
    client.write_table(
        "taxi_table", make_taxi_data(N_ROWS, rng), schema=TAXI_SCHEMA
    )


def _normalize(events):
    out = []
    for e in events:
        d = e.to_json_dict()
        if d["kind"] in _TIMER_KINDS:
            continue
        for f in _TIMING_FIELDS:
            d.pop(f, None)
        out.append(json.dumps(d, sort_keys=True))
    return sorted(out)


# --------------------------------------------------------------- event bus
def test_bus_bounded_buffer_drop_accounting():
    bus = EventBus()
    slow = bus.subscribe(maxlen=4)
    fast = bus.subscribe(maxlen=100)
    for i in range(10):
        bus.publish(StageQueued(run_id=1, stage_id=i))
    # the slow consumer lost ITS oldest six; the fast one lost nothing
    kept = slow.poll()
    assert [e.stage_id for e in kept] == [6, 7, 8, 9]
    assert slow.dropped == 6
    assert len(fast.poll()) == 10 and fast.dropped == 0
    stats = bus.stats()
    assert stats["published"] == 10 and stats["dropped"] == 6
    slow.close()
    assert bus.stats()["subscribers"] == 1


def test_bus_seq_is_monotonic_per_run_scope():
    bus = EventBus()
    sub = bus.subscribe()
    for run_id in (1, 2, 1, None, 2, 1, None):
        bus.publish(StageQueued(run_id=run_id))
    by_scope = {}
    for e in sub.poll():
        by_scope.setdefault(e.run_id, []).append(e.seq)
    assert by_scope[1] == [1, 2, 3]
    assert by_scope[2] == [1, 2]
    assert by_scope[None] == [1, 2]  # global scope for run-less events


def test_event_json_roundtrip_all_kinds():
    for kind, cls in EVENT_TYPES.items():
        ev = cls(run_id=3)
        back = event_from_json_dict(ev.to_json_dict())
        assert type(back) is cls and back.run_id == 3
    # unknown kinds / fields degrade instead of failing the reader
    degraded = event_from_json_dict(
        {"kind": "FromTheFuture", "run_id": 9, "novel_field": 1}
    )
    assert type(degraded).__name__ == "Event" and degraded.run_id == 9
    known = event_from_json_dict(
        {"kind": "RunFinished", "state": "ERROR", "novel_field": 1}
    )
    assert isinstance(known, RunFinished) and known.state == "ERROR"


def test_spool_survives_rotation_and_filters_by_run(tmp_path):
    spool = tmp_path / "events.jsonl"
    bus = EventBus(spool_path=spool, spool_max_bytes=600)
    for i in range(12):
        bus.publish(ScanShardRead(run_id=i % 2, shard_index=i))
    bus.close()
    assert spool.with_name(spool.name + ".1").exists()  # rotated at 600B
    # retention is bounded (live file + one rotated predecessor), so the
    # readable window is a contiguous SUFFIX of the stream — never a gap
    got = [e.shard_index for e in read_spool(spool)]
    assert got == list(range(12))[-len(got):] and got[-1] == 11
    only_run1 = [e.shard_index for e in read_spool(spool, run_id=1)]
    assert only_run1 == [i for i in got if i % 2 == 1]
    assert len(read_spool(spool, limit=2)) == 2


def test_metrics_registry_counters_gauges_histograms():
    m = MetricsRegistry()
    m.counter("executor.tasks").inc()
    m.counter("executor.tasks").inc(4)
    m.gauge("pool.size").set(8)
    for v in range(100):
        m.histogram("lat").observe(float(v))
    snap = m.snapshot()
    assert snap["counters"]["executor.tasks"] == 5
    assert snap["gauges"]["pool.size"] == 8
    hist = snap["histograms"]["lat"]
    assert hist["count"] == 100
    assert hist["p50"] == pytest.approx(49.5, abs=2.0)
    assert hist["max"] == 99.0


# ----------------------------------------------- determinism across knobs
def test_event_set_is_parallelism_invariant():
    """Parallelism 1 (sequential baseline) vs 2 vs 8 on fresh lakes: the
    same multiset of events modulo timestamps/seq/interleaving."""
    normalized = {}
    for p in PARALLELISMS:
        with _client(p) as client:
            _write_taxi(client)
            handle = client.run(
                build_fanout_pipeline(), fusion=False, pushdown=False,
                parallelism=p,
            ).raise_for_state()
            normalized[p] = _normalize(client.runlog.get(handle.run_id))
    base = normalized[PARALLELISMS[0]]
    assert len(base) > 10  # a real stream, not a trivial pass
    for p in PARALLELISMS[1:]:
        assert normalized[p] == base


# ------------------------------------------------------------ span nesting
def test_trace_spans_nest_and_cover_the_run():
    with _client(8) as client:
        _write_taxi(client)
        handle = client.run(
            build_fanout_pipeline(), fusion=False, pushdown=False,
            parallelism=8,
        ).raise_for_state()
        trace = handle.trace()
    root = trace.root
    assert root.kind == "run" and trace.state == "SUCCESS"
    eps = 0.05  # time.time() starts vs perf_counter durations
    for span in root.walk():
        assert span.start >= root.start - eps
        assert span.end <= root.end + eps
        assert span.end >= span.start
    for sid, spans in trace.stage_spans.items():
        q, ex = spans["queue"], spans["exec"]
        # queue hands off exactly where exec picks up
        assert q.end == ex.start
        for child in ex.children:
            assert child.kind in ("phase", "scan", "node")
            assert child.start >= ex.start - eps
            assert child.end <= ex.end + eps
        # every logical node appears inside its stage's exec window
        nodes = {c.name for c in ex.children if c.kind == "node"}
        assert nodes == {f"node {n}" for n in q.attrs["nodes"]}
    # the two-parent stage's dependency edges survived into the trace
    assert any(len(ps) >= 2 for ps in trace.stage_parents.values())
    assert trace.critical_path(), "critical path must be non-empty"
    assert trace.coverage() >= 0.90
    # Chrome export is self-consistent
    chrome = trace.to_chrome_trace()
    names = {e["name"] for e in chrome["traceEvents"] if e["ph"] == "X"}
    assert f"run {trace.run_id}" in names
    assert all(
        {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        for e in chrome["traceEvents"] if e["ph"] == "X"
    )


def test_warm_run_traces_as_rehydrate_spans():
    """Acceptance bar: a warm run's cache hits appear as rehydrate spans
    and the trace still accounts for >=95% of wall-clock."""
    with _client(4) as client:
        _write_taxi(client)
        p = build_fanout_pipeline()
        client.run(p, fusion=False, pushdown=False).raise_for_state()
        warm = client.run(p, fusion=False, pushdown=False).raise_for_state()
        assert warm.cache["rehydrated"] >= 1  # it genuinely hit the cache
        trace = warm.trace()
    rehydrate = [s for s in trace.root.walk() if s.kind == "rehydrate"]
    assert len(rehydrate) == warm.cache["rehydrated"]
    assert all(s.attrs["bytes"] > 0 for s in rehydrate)
    assert trace.coverage() >= 0.95


# ------------------------------------------------------- failure semantics
def test_audit_failure_still_emits_run_finished():
    """A mid-DAG expectation failure rolls the run back — but the trace
    is still persisted and RunFinished carries the failure."""
    with _client(8) as client:
        _write_taxi(client)
        handle = client.run(
            build_fanout_pipeline(threshold=10_000.0),
            fusion=False, pushdown=False, parallelism=8, raise_errors=False,
        )
        assert handle.state is RunState.AUDIT_FAILED
        events = client.runlog.get(handle.run_id)
        trace = handle.trace()
    finished = [e for e in events if isinstance(e, RunFinished)]
    assert len(finished) == 1
    assert finished[0].state == "AUDIT_FAILED"
    assert finished[0].failed_checks == ["trips_expectation"]
    assert trace.state == "AUDIT_FAILED"


def test_infra_error_still_emits_run_finished():
    with _client(2) as client:
        p = Pipeline("missing_source")
        p.sql("x", "SELECT pickup_at FROM no_such_table")
        handle = client.run(p, raise_errors=False)
        assert handle.state is RunState.ERROR
        # the captured exception still addresses its run (and its trace)
        assert handle.run_id > 0
        events = client.runlog.get(handle.run_id)
        assert handle.trace().state == "ERROR"
    finished = [e for e in events if isinstance(e, RunFinished)]
    assert len(finished) == 1 and finished[0].state == "ERROR"


def test_telemetry_off_is_supported_and_runs_still_work():
    with Client.ephemeral(telemetry=False) as client:
        _write_taxi(client)
        handle = client.run(
            build_fanout_pipeline(), fusion=False, pushdown=False
        ).raise_for_state()
        assert client.bus is None
        with pytest.raises(RuntimeError):
            client.events(follow=True)
        # no bus -> no collected events -> no persisted trace
        assert not client.runlog.has(handle.run_id)


# ------------------------------------------------------------- query path
def test_query_emits_scan_and_query_events():
    with _client(2) as client:
        _write_taxi(client)
        sub = client.events(follow=True)
        rows = client.query("SELECT COUNT(*) AS n FROM taxi_table")
        assert int(rows["n"][0]) == N_ROWS
        events = sub.poll()
        sub.close()
    scans = [e for e in events if e.kind == "ScanShardRead"]
    queries = [e for e in events if e.kind == "QueryExecuted"]
    assert scans and all(s.source == "query" for s in scans)
    assert len(queries) == 1
    assert queries[0].table == "taxi_table"
    assert queries[0].shards_read == len(scans)


# ------------------------------------------------------------- runlog GC
def _backdate_runlog_ref(client: Client, run_id: int, by_s: float) -> str:
    """Age a trace ref in place; returns its blob key."""
    ref = client.store.get_ref("runlog", f"run_{run_id}")
    ref["created_at"] -= by_s
    client.store.set_ref("runlog", f"run_{run_id}", ref)
    return ref["blob"]


def test_runlog_gc_ttl_sweeps_expired_keeps_live():
    with _client(2) as client:
        _write_taxi(client)
        p = build_fanout_pipeline()
        old = client.run(p, fusion=False, pushdown=False).raise_for_state()
        live = client.run(p, fusion=False, pushdown=False).raise_for_state()
        old_blob = _backdate_runlog_ref(client, old.run_id, 30 * 86400.0)
        live_blob = client.store.get_ref("runlog", f"run_{live.run_id}")["blob"]

        # dry run reports but does not touch
        report = client.gc(
            runlog_ttl_s=7 * 86400.0, grace_s=0.0, dry_run=True
        )
        assert report.swept_runlog_refs == 1
        assert client.runlog.has(old.run_id)

        report = client.gc(runlog_ttl_s=7 * 86400.0, grace_s=0.0)
        assert report.swept_runlog_refs == 1
        # expired: ref gone AND blob reclaimed on the same pass
        assert not client.runlog.has(old.run_id)
        with pytest.raises(KeyError):
            client.runlog.get(old.run_id)
        assert not client.store.exists(old_blob)
        # live: still readable, bytes still pinned
        assert client.store.exists(live_blob)
        assert client.trace(live.run_id).state == "SUCCESS"

        # ttl=None retains everything
        report = client.gc(runlog_ttl_s=None, grace_s=0.0)
        assert report.swept_runlog_refs == 0
        assert client.runlog.has(live.run_id)


# -------------------------------------------------------------------- CLI
PIPELINE_SRC = '''
from repro.core import Pipeline

PIPELINE = Pipeline("cli_telemetry")
PIPELINE.sql(
    "trips",
    "SELECT pickup_location_id, passenger_count as count FROM taxi_table "
    "WHERE pickup_at >= '2019-04-01'",
)

@PIPELINE.python
def trips_expectation(ctx, trips):
    return trips.mean("count") > 1.0
'''


@pytest.fixture
def cli_lake(tmp_path, rng):
    from repro.catalog import Catalog
    from repro.io import ObjectStore
    from repro.table import TableFormat

    root = tmp_path / "lake"
    store = ObjectStore(root)
    fmt = TableFormat(store)
    snap = fmt.write("taxi_table", TAXI_SCHEMA, make_taxi_data(500, rng))
    Catalog(store).commit("main", {"taxi_table": fmt.manifest_key(snap)})
    pipeline_file = tmp_path / "pipeline.py"
    pipeline_file.write_text(PIPELINE_SRC)
    return root, pipeline_file


def _json_payload(out: str) -> dict:
    return json.loads(out[out.index("{"):])


def test_cli_run_json_summary(cli_lake, capsys):
    root, pipeline_file = cli_lake
    main(["--lake", str(root), "run", str(pipeline_file), "--json"])
    payload = _json_payload(capsys.readouterr().out)
    assert payload["state"] == "SUCCESS"
    assert payload["run_id"] == 1 and payload["failed_checks"] == []
    assert payload["checks"] == {"trips_expectation": True}
    assert "trips" in payload["artifacts"]
    timings = payload["stage_timings"]
    assert timings and all(
        {"queue_s", "exec_s", "commit_s"} <= set(v) for v in timings.values()
    )
    assert {"hits", "rehydrated"} <= set(payload["cache"])
    assert payload["io"]["puts"] > 0 and payload["wall_s"] > 0


def test_cli_run_json_audit_failure_exits_2(cli_lake, tmp_path, capsys):
    root, _ = cli_lake
    failing = tmp_path / "failing.py"
    failing.write_text(PIPELINE_SRC.replace("> 1.0", "> 10_000.0"))
    with pytest.raises(SystemExit) as exc:
        main(["--lake", str(root), "run", str(failing), "--json"])
    assert exc.value.code == 2
    payload = _json_payload(capsys.readouterr().out)
    assert payload["state"] == "AUDIT_FAILED"
    assert payload["failed_checks"] == ["trips_expectation"]


def test_cli_trace_and_chrome_export(cli_lake, tmp_path, capsys):
    root, pipeline_file = cli_lake
    main(["--lake", str(root), "run", str(pipeline_file)])
    capsys.readouterr()
    chrome_path = tmp_path / "trace.json"
    main(["--lake", str(root), "trace", "1", "--chrome", str(chrome_path)])
    out = capsys.readouterr().out
    assert "run 1" in out and "critical path" in out and "coverage" in out
    chrome = json.loads(chrome_path.read_text())
    assert any(e["ph"] == "X" for e in chrome["traceEvents"])
    assert chrome["otherData"]["state"] == "SUCCESS"
    # unknown run id -> clean error, not a stack trace
    with pytest.raises(SystemExit):
        main(["--lake", str(root), "trace", "999"])


def test_cli_events_reads_spool(cli_lake, capsys):
    root, pipeline_file = cli_lake
    main(["--lake", str(root), "run", str(pipeline_file)])
    capsys.readouterr()
    main(["--lake", str(root), "events"])
    out = capsys.readouterr().out
    assert "RunStarted" in out and "RunFinished" in out
    main(["--lake", str(root), "events", "--limit", "2"])
    limited = capsys.readouterr().out.strip().splitlines()
    assert len(limited) == 2
    main(["--lake", str(root), "gc", "--dry-run", "--runlog-ttl", "0.001"])
    out = capsys.readouterr().out
    assert "1 run traces" in out
