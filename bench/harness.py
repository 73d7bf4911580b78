"""One run of one benchmark cell, driven by data.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
harness finds the rest by those names:

* ``configs/<config>.json`` holds the configuration's sizes, and
  ``configs/<config>.py`` beside it generates its tables from the seed;
* ``mixes/<traffic>.json`` holds the mix: its statements (SQL for the
  program, a structured form for the reference, which may join tables
  and cut at a LIMIT: ``reference.py`` gives the form), their weights
  and parameters, the number of clients, and the limits of the
  comparison;
* ``metrics/<metric>.py`` reads one metric from what a run measured.

A run builds a lake in a temporary directory, writes the tables, warms
every statement of the mix once, then measures for ``seconds``.  A
query mix offers ``Client.query`` requests in an open loop at the mix's
fixed rate, served by its workers, threads that set-up starts and that
each run every request once before the window opens; a pipeline mix
has one client run ``Client.run`` back to back.  Once the window has closed and every
request has returned, the answers are compared with the numpy reference
(``reference.py``).
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import queue
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, Iterator, List, Mapping, Optional, Tuple

import numpy as np

import devicetrace
import reference
import work

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
BENCHMARK = ROOT / "BENCHMARK.json"
#: seconds a request may take past the window's close before it counts as
#: never answered
DRAIN_S = 60.0
#: JAX's event for a program handed to XLA (compiled, or loaded from the
#: persistent cache)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


def pin_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout, so that only a cell's first run there compiles, and keep
    every program, however quick its compile, so that later set-ups load
    them all.  Called before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".bench_jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- definitions
def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    generator: ModuleType
    mix: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    def generate(self, seed: int) -> Dict[str, Dict[str, np.ndarray]]:
        return self.generator.generate(self.config, seed)

    def metrics(self, traced: bool) -> List[Dict[str, Any]]:
        """The metrics this cell reports: end-to-end ones untraced, the
        per-layer ones traced."""
        if not traced:
            return [m for m in self.end_to_end
                    if self.name in m.get("workloads", [self.name])]
        reported = {m["name"] for m in self.metrics(False)}
        return [m for m in self.per_layer
                if self.name in m["workloads"] or
                ("workloads" not in m and m["moves"] in reported)]


def load_cell(name: str, benchmark: Path = BENCHMARK,
              config_overrides: Optional[Mapping[str, Any]] = None) -> Cell:
    spec = json.loads(Path(benchmark).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark}")
    cell = cells[name]
    config_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config_path = ROOT / config_entry["file"]
    config = json.loads(config_path.read_text())
    config.update(config_overrides or {})
    return Cell(
        name=name,
        chips=int(cell["chips"]),
        config=config,
        generator=load_module(config_path.with_suffix(".py")),
        mix=json.loads((BENCH / "mixes" / f"{cell['traffic']}.json").read_text()),
        end_to_end=spec["end_to_end"],
        per_layer=spec["per_layer"],
    )


# ----------------------------------------------------------------- traffic
class Traffic:
    """The general request generator of a query mix.

    Requests are dealt from a deck shuffled from the seed: every request
    of the mix ``weight`` times, reshuffled when used up; a request with
    ``params`` takes them in turn from a shuffled deck of its own.  So
    every seed sends the same proportions, in another order.  A mix with
    an ``order_seed`` goes further: every seed sends the same sequence,
    rotated (``plan``).
    """

    def __init__(self, mix: Mapping[str, Any], seed: int):
        self.mix = mix
        self.requests = mix["requests"]
        self.seed = seed % 2**64

    def name(self, r: int) -> str:
        return self.requests[r]["name"]

    def op(self, r: int) -> str:
        """The request's operation type, which latency is reported by."""
        return self.requests[r].get("op", self.name(r))

    def params(self, r: int, p: Optional[int]) -> Dict[str, str]:
        return {} if p is None else self.requests[r]["params"][p]

    def sql(self, r: int, p: Optional[int]) -> str:
        return self.requests[r]["sql"].format(**self.params(r, p))

    def statement(self, r: int) -> Dict[str, Any]:
        return self.requests[r]["statement"]

    def every(self) -> List[Tuple[int, Optional[int]]]:
        """Each distinct statement once."""
        return [(r, p) for r, req in enumerate(self.requests)
                for p in (range(len(req["params"])) if "params" in req else [None])]

    def plan(self, n: int) -> List[Tuple[int, Optional[int]]]:
        """The ``n`` requests of a window, in the order they are sent.

        A mix with an ``order_seed`` sends one fixed sequence of requests
        (the deck as shuffled by ``order_seed``) for every seed, rotated to
        start where the seed picks: every seed then sends the same
        requests beside the same neighbours, and only the parameters each
        request takes, dealt from its shuffled deck, follow the seed."""
        if "order_seed" not in self.mix:
            stream = self.stream()
            return [next(stream) for _ in range(n)]
        order = Traffic(self.mix, int(self.mix["order_seed"])).stream()
        fixed = [next(order)[0] for _ in range(n)]
        rng = np.random.default_rng(self.seed)
        start = int(rng.integers(n))
        decks: Dict[int, List[int]] = {}
        plan = []
        for r in fixed[start:] + fixed[:start]:
            if "params" not in self.requests[r]:
                plan.append((r, None))
                continue
            if not decks.get(r):
                decks[r] = [int(i) for i in rng.permutation(len(self.requests[r]["params"]))]
            plan.append((r, decks[r].pop()))
        return plan

    def stream(self) -> Iterator[Tuple[int, Optional[int]]]:
        rng = np.random.default_rng(self.seed)
        deck = [r for r, req in enumerate(self.requests) for _ in range(req["weight"])]
        param_decks: Dict[int, List[int]] = {}
        while True:
            for r in rng.permutation(deck):
                r = int(r)
                if "params" not in self.requests[r]:
                    yield r, None
                    continue
                if not param_decks.get(r):
                    param_decks[r] = [int(i) for i in
                                      rng.permutation(len(self.requests[r]["params"]))]
                yield r, param_decks[r].pop()


# -------------------------------------------------------------- pipelines
def build_pipeline(spec: Mapping[str, Any]):
    """The mix's pipeline, node by node, as a user declares it."""
    from repro import Pipeline
    from repro.core.pipeline import Node

    pipeline = Pipeline(spec["name"])
    for node in spec["nodes"]:
        if node["kind"] == "sql":
            pipeline.sql(node["name"], node["sql"])
        else:
            pipeline.add_node(Node(
                name=node["name"], kind="expectation", parents=(node["input"],),
                fn=_expectation(node["stat"], node["column"], node["op"], node["value"]),
                requirements=dict(node.get("requirements", {})),
            ))
    return pipeline


def _expectation(stat: str, column: str, op: str, value: float) -> Callable:
    compare = reference.COMPARE[op]

    def check(ctx, table):
        return compare(getattr(table, stat)(column), value)

    return check


# ------------------------------------------------------------ measurement
class CompileLog:
    """Programs JAX hands to XLA, and persistent-cache misses, with the
    host time at which each was reported."""

    def __init__(self):
        self.compiles: List[Tuple[float, str]] = []
        self.misses: List[float] = []

    def _on_duration(self, event: str, duration: float, **kwargs: Any) -> None:
        if event == BACKEND_COMPILE:
            self.compiles.append((time.perf_counter(), str(kwargs.get("fun_name", ""))))

    def _on_event(self, event: str, **kwargs: Any) -> None:
        if event == CACHE_MISS:
            self.misses.append(time.perf_counter())

    @contextlib.contextmanager
    def listening(self) -> Iterator["CompileLog"]:
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        try:
            yield self
        finally:
            monitoring.unregister_event_duration_listener(self._on_duration)
            monitoring.unregister_event_listener(self._on_event)

    def between(self, lo: float, hi: float) -> List[str]:
        return [name for t, name in self.compiles if lo <= t <= hi]


@dataclass
class Request:
    request: int
    param: Optional[int]
    #: the operation type (a query's ``op`` in the mix, or ``run``)
    op: str
    #: when the request was due (a query) or started (a pipeline run)
    start: float
    end: float
    answer: Any  # the result, or the exception it raised
    #: when a worker made the call (a query)
    called: float = 0.0
    #: the ``QueryExecuted`` event the call published (a query)
    event: Any = None
    #: when the sender handed the request to the workers (a query)
    sent: float = 0.0


@dataclass
class Measured:
    """What one run measured; each metric's reader takes what it needs."""

    kind: str  # "query" | "run"
    setup_s: float
    window_open: float
    window_close: float
    requests: List[Request]
    events: List[Any]
    trace: Optional[devicetrace.TraceSummary] = None
    #: least device seconds of each request in the traced window
    least_times: List[float] = field(default_factory=list)

    @property
    def latencies(self) -> List[float]:
        return [r.end - r.start for r in self.requests]

    def latencies_of(self, op: str) -> List[float]:
        return [r.end - r.start for r in self.requests if r.op == op]

    def events_of(self, kind: str) -> List[Any]:
        return [e for e in self.events if type(e).__name__ == kind]

    def idle_percent(self) -> Optional[float]:
        """Share of the traced window in which no operation ran on the device."""
        return None if self.trace is None else 100.0 * self.trace.idle_share

    def roofline_percent(self) -> Optional[float]:
        """Share of the device's busy time that the traced window's queries
        need at least: the sum over requests of max(bytes / HBM peak,
        operations / peak rate), counted from each statement and the rows
        its scan handed over (``work.py``), over the device busy time."""
        if self.trace is None or not self.least_times or self.trace.busy_s <= 0:
            return None
        return 100.0 * sum(self.least_times) / self.trace.busy_s

    def query_events(self, op: str) -> List[Any]:
        """The ``QueryExecuted`` events of the requests of one operation type."""
        return [r.event for r in self.requests if r.op == op and r.event is not None]


def attribute(requests: List[Request], events: List[Any], clock_offset: float) -> None:
    """Give each answered query the ``QueryExecuted`` event its call
    published.  A call publishes its event in the worker's thread just
    before it returns, so the event whose wall-clock stamp lies nearest
    the return, and whose ``wall_s`` lies nearest the call's length, is
    the call's.  ``clock_offset`` is ``time.time() - time.perf_counter()``."""
    pending = [e for e in events if type(e).__name__ == "QueryExecuted"]
    answered = [r for r in requests if not isinstance(r.answer, BaseException)]
    for req in sorted(answered, key=lambda r: r.end):
        if not pending:
            break
        returned, took = req.end + clock_offset, req.end - req.called
        req.event = min(pending, key=lambda e: abs(returned - e.ts) + abs(took - e.wall_s))
        pending.remove(req.event)


def annotate(traced: bool, name: str):
    if not traced:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(devicetrace.ANNOTATION_PREFIX + name)


class Workers:
    """The threads that serve a query mix's windows, started in set-up.

    Each thread runs every request of the mix once (its first parameters)
    before the first window opens: a thread's first query is slower than
    its later ones (TPC-H Q1 on a TPU v5e host: 0.56-0.60 s against
    0.41-0.46 s), and without this the window's first request on each
    thread would pay for it."""

    def __init__(self, client, traffic: Traffic, n: int, traced: bool):
        self.client, self.traffic, self.traced = client, traffic, traced
        self.inbox: "queue.Queue[Optional[tuple]]" = queue.Queue()
        self.errors: List[BaseException] = []
        ready = threading.Barrier(n + 1)
        self.threads = [threading.Thread(target=self._serve, args=(ready,), daemon=True,
                                         name=f"bench-worker-{w}") for w in range(n)]
        for t in self.threads:
            t.start()
        ready.wait()
        if self.errors:
            self.close()
            raise self.errors[0]

    def _serve(self, ready: threading.Barrier) -> None:
        traffic = self.traffic
        try:
            for r, req in enumerate(traffic.requests):
                self.client.query(traffic.sql(r, 0 if "params" in req else None))
        except Exception as e:
            self.errors.append(e)
        finally:
            ready.wait()
        while (item := self.inbox.get()) is not None:
            r, p, due, sent, out, done = item
            called = time.perf_counter()
            try:
                with annotate(self.traced, traffic.name(r)):
                    answer = self.client.query(traffic.sql(r, p))
            except Exception as e:  # an answer that never came is judged
                answer = e
            out.append(Request(r, p, traffic.op(r), due, time.perf_counter(), answer,
                               called, sent=sent))
            done.release()

    def close(self) -> None:
        for _ in self.threads:
            self.inbox.put(None)
        for t in self.threads:
            t.join(timeout=DRAIN_S)


def _query_window(workers: Workers, rate: float, seconds: float,
                  ) -> Tuple[float, float, List[Request], int, float]:
    """Open loop: request ``k`` is due ``k / rate`` seconds after the open,
    for ``seconds``, whatever the system does; the ``workers`` serve the
    requests in the order they fall due.  A request's latency runs from
    when it was due to its return, so a wait for a free worker counts.
    Returns the window's open, the time the last request returned (or
    the close), the requests, how many never returned within ``DRAIN_S``
    of the close, and how late the sender ran at most."""
    plan = workers.traffic.plan(max(1, round(rate * seconds)))
    out: List[Request] = []
    done = threading.Semaphore(0)
    t_open = time.perf_counter()
    late = 0.0
    for k, (r, p) in enumerate(plan):
        due = t_open + k / rate
        if (wait := due - time.perf_counter()) > 0:
            time.sleep(wait)
        sent = time.perf_counter()
        late = max(late, sent - due)
        workers.inbox.put((r, p, due, sent, out, done))
    deadline = t_open + seconds
    for _ in plan:
        if not done.acquire(timeout=max(0.0, deadline + DRAIN_S - time.perf_counter())):
            break
    requests = list(out)
    hung = len(plan) - len(requests)
    close = max([r.end for r in requests], default=deadline)
    return t_open, max(close, deadline), requests, hung, late


def _run_window(client, pipeline, spec: Mapping[str, Any], seconds: float,
                traced: bool) -> Tuple[float, float, List[Request]]:
    """One client running the pipeline back to back for ``seconds``; the
    run that is under way at the close finishes and counts."""
    requests: List[Request] = []
    t_open = time.perf_counter()
    while time.perf_counter() < t_open + seconds:
        t0 = time.perf_counter()
        try:
            with annotate(traced, "run"):
                handle = client.run(pipeline, branch=spec["branch"], cache=spec["cache"])
        except Exception as e:
            handle = e
        requests.append(Request(0, None, "run", t0, time.perf_counter(), handle))
    return t_open, requests[-1].end, requests


# -------------------------------------------------------------- judgement
def query_answers(traffic: Traffic, keys, tables, precision: str = "exact"):
    """The reference's whole ordered answer to each ``(request, param)``,
    before its LIMIT (``reference.head`` cuts it)."""
    return {
        (r, p): reference.run_statement(traffic.statement(r), tables,
                                        traffic.params(r, p), precision)
        for r, p in keys
    }


def judge_queries(mix, traffic: Traffic, requests: List[Request], hung: int,
                  want: Mapping) -> Tuple[Dict[str, float], int]:
    """Every answer against the reference.  The numbers compared are the
    integer cells that differ (with ORDER BY violations, and every
    reference row of an answer that never came), and, where the mix sets
    a limit on it, the largest relative error of a float cell.  Returns
    the numbers and how many requests failed."""
    limits = mix["limits"]
    wrong, rel, failed = 0, 0.0, hung
    for req in requests:
        ref, stmt = want[(req.request, req.param)], traffic.statement(req.request)
        if isinstance(req.answer, BaseException):
            wrong += max(reference.kept_rows(stmt, reference.rows(ref)), 1)
            failed += 1
            continue
        w, r = reference.compare(req.answer, ref, stmt, limits.get("float_rel_err", 0.0))
        wrong += w
        failed += bool(w) or r > limits.get("float_rel_err", float("inf"))
        rel = max(rel, r)
    numbers = {"wrong_cells": float(wrong + hung)}
    if "float_rel_err" in limits:
        numbers["float_rel_err"] = rel
    return numbers, failed


def pipeline_reference(spec, tables, precision: str = "exact"):
    """The reference's whole ordered answer for each SQL node, and each
    expectation's verdict.  A node reads its parents' answers as SQL
    gives them, cut at their LIMIT."""
    env = dict(tables)
    answers, verdicts = {}, {}
    for node in spec["nodes"]:
        if node["kind"] == "sql":
            answers[node["name"]] = reference.run_statement(
                node["statement"], env, precision=precision)
            env[node["name"]] = reference.head(answers[node["name"]], node["statement"])
        else:
            data = env[node["input"]][node["column"]]
            if precision == "bf16":
                data = data.astype(np.float32).astype(reference.BF16)
            verdicts[node["name"]] = reference.run_expectation(
                node, {node["input"]: {node["column"]: data}})
    return answers, verdicts


def judge_runs(spec, requests: List[Request], read_back, head_log: List[str],
               answers, verdicts) -> Tuple[Dict[str, float], int]:
    """Each run's state and expectation verdicts, every run's merge into
    the branch, and the artifacts read back from the branch's head,
    against the reference's ``answers`` (``pipeline_reference``)."""
    failed = 0
    merged = []
    for req in requests:
        handle = req.answer
        ok = not isinstance(handle, BaseException) and \
            handle.state.name == "SUCCESS" and \
            all(handle.checks.get(n) == v for n, v in verdicts.items())
        failed += not ok
        merged.append(None if isinstance(handle, BaseException) else handle.merged_commit)
    # each run's merge is its own commit, and the last is the head read back
    distinct = len({m for m in merged if m})
    unmerged = len(merged) - distinct + sum(m not in head_log for m in merged if m)
    if merged and merged[-1] and head_log and head_log[0] != merged[-1]:
        unmerged += 1
    wrong = 0
    nodes = {n["name"]: n for n in spec["nodes"]}
    for name in spec["read_back"]:
        got, stmt = read_back.get(name), nodes[name]["statement"]
        if got is None:
            wrong += max(reference.kept_rows(stmt, reference.rows(answers[name])), 1)
            continue
        wrong += reference.compare(got, answers[name], stmt)[0]
    return {"wrong_cells": float(wrong), "wrong_runs": float(failed + unmerged)}, \
        failed


# ---------------------------------------------------------------- the run
def check_chips(chips: int) -> Any:
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no device: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def prepare(cell: Cell, tables, seed: int, client, started: float,
            log: Callable[[str], None]) -> Tuple[Optional[Traffic], Any]:
    """Set-up: the tables made from the seed written to the client's lake,
    and every statement of the mix (or the pipeline) run once."""
    import repro

    mix = cell.mix
    for table, data in tables.items():
        schema = repro.Schema.of(**cell.config["tables"][table]["columns"])
        client.write_table(table, data, schema=schema)
    log(f"written {', '.join(f'{t}: {len(next(iter(c.values())))} rows' for t, c in tables.items())} "
        f"({time.perf_counter() - started:.3f} s)")
    if mix["kind"] == "query":
        traffic = Traffic(mix, seed)
        for r, p in traffic.every():
            client.query(traffic.sql(r, p))
        return traffic, None
    pipeline = build_pipeline(mix["pipeline"])
    client.run(pipeline, branch=mix["pipeline"]["branch"],
               cache=mix["pipeline"]["cache"]).raise_for_state()
    return None, pipeline


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             started: float, require_chip: bool = True,
             log: Callable[[str], None] = lambda s: None,
             dump: Optional[Path] = None) -> Dict[str, Any]:
    """Set up, measure and judge one run; the result line as a dict.
    ``dump`` names a file to write each request's times to, one JSON
    object a line, for looking into a run's spread."""
    # the tables are made while JAX reaches the chip
    with ThreadPoolExecutor(1, thread_name_prefix="bench-generate") as pool:
        generating = pool.submit(cell.generate, seed)
        import jax

        devices = check_chips(cell.chips) if require_chip else jax.devices()
        log(f"{len(devices)} {devices[0].platform} device(s) "
            f"({time.perf_counter() - started:.3f} s)")
        tables = generating.result()
    import repro

    mix = cell.mix
    kind = mix["kind"]
    compiles = CompileLog()
    with compiles.listening(), tempfile.TemporaryDirectory(prefix="bench_") as tmp:
        client = repro.Client(Path(tmp) / "lake")
        workers = None
        try:
            traffic, pipeline = prepare(cell, tables, seed, client, started, log)
            if kind == "query":
                workers = Workers(client, traffic, int(mix["workers"]), traced)
            log(f"warmed ({time.perf_counter() - started:.3f} s, {len(compiles.compiles)} "
                f"programs handed to XLA, {len(compiles.misses)} persistent-cache misses)")

            trace_dir = Path(tmp) / "trace"
            if traced:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1  # the benchmark's own spans
                jax.profiler.start_trace(str(trace_dir), profiler_options=options)
            with client.events(follow=True, buffer=1 << 20) as sub, \
                    annotate(traced, "window"):
                if kind == "query":
                    t_open, t_close, requests, hung, late = _query_window(
                        workers, float(mix["rate_per_s"]), seconds)
                else:
                    t_open, t_close, requests = _run_window(
                        client, pipeline, mix["pipeline"], seconds, traced)
                    hung, late = 0, 0.0
                events = sub.poll()
            setup_s = t_open - started
            attribute(requests, events, time.time() - time.perf_counter())
            in_window = compiles.between(t_open, t_close)
            summary = None
            if traced:
                jax.profiler.stop_trace()
                summary = devicetrace.reduce(devicetrace.load(str(trace_dir)))
            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices[:cell.chips])
            read_back, head_log = {}, []
            if kind == "run":
                spec = mix["pipeline"]
                heads = client.tables(spec["branch"])
                for name in spec["read_back"]:
                    if name in heads:
                        read_back[name] = client.fmt.read(client.fmt.load_snapshot(heads[name]))
                head_log = [c.commit_id for c in
                            client.log(spec["branch"], limit=len(requests) + 16)]
        finally:
            if workers is not None:
                workers.close()
            client.close()

    log(f"sender ran late by at most {1e3 * late:.3f} ms")
    log(f"compiles in window: {len(in_window)}"
        + (f" ({', '.join(sorted(set(in_window)))})" if in_window else ""))
    # the reference runs once the window has closed and the program's
    # state is gone
    if kind == "query":
        want = query_answers(traffic, {(r.request, r.param) for r in requests}, tables)
        numbers, failed = judge_queries(mix, traffic, requests, hung, want)
    else:
        answers, verdicts = pipeline_reference(mix["pipeline"], tables)
        numbers, failed = judge_runs(mix["pipeline"], requests, read_back,
                                     head_log, answers, verdicts)
    limits = mix["limits"]
    correct = all(numbers[k] <= limits[k] for k in numbers)

    least: List[float] = []
    if traced and kind == "query":
        peak_rates = work.peaks(devices[0].device_kind) if require_chip else None
        least = _least_times(cell, traffic, requests, tables, want, peak_rates)
    measured = Measured(kind, setup_s, t_open, t_close, requests, events,
                        summary, least)
    if dump is not None:
        write_dump(dump, measured)
    metrics = {}
    for spec in cell.metrics(traced):
        value = load_module(BENCH / "metrics" / f"{spec['name']}.py").read(measured)
        if value is not None:
            metrics[spec["name"]] = {"value": float(value), "unit": spec["unit"]}

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(peak),
    }
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": len(requests) + hung,
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["compared"] = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    result["compiles_in_window"] = len(in_window)
    return result


def write_dump(path: Path, run: Measured) -> None:
    """Each request's operation, statement and times from the window's
    open: due, sent (a query), called, returned; and its phases."""
    with open(path, "w") as f:
        for r in sorted(run.requests, key=lambda r: r.start):
            row = {"op": r.op, "request": r.request, "param": r.param,
                   "due": r.start - run.window_open, "end": r.end - run.window_open}
            if r.sent:
                row["sent"] = r.sent - run.window_open
                row["called"] = r.called - run.window_open
            if r.event is not None:
                row.update({k: getattr(r.event, k) for k in
                            ("parse_s", "plan_s", "scan_s", "exec_s", "wall_s")})
            f.write(json.dumps(row) + "\n")


def _least_times(cell: Cell, traffic: Traffic, requests: List[Request], tables,
                 want, peak_rates) -> List[float]:
    """Least device time of each request, from its statement, the rows of
    each of its tables that its own conjuncts keep, the rows that survive
    its joins, and the rows of its answer."""
    if peak_rates is None:
        return []
    cost: Dict[Tuple[int, Optional[int]], float] = {}
    for key in {(r.request, r.param) for r in requests}:
        stmt, params = traffic.statement(key[0]), traffic.params(*key)
        dtypes = {t: {c: a.dtype for c, a in tables[t].items()}
                  for t in reference.statement_tables(stmt)}
        ops, nbytes = work.statement_work(
            stmt, dtypes, reference.table_rows(stmt, tables, params),
            reference.selected_rows(stmt, tables, params),
            reference.kept_rows(stmt, reference.rows(want[key])))
        cost[key] = work.least_time(ops, nbytes, peak_rates)
    return [cost[(r.request, r.param)] for r in requests]


def main(argv: Optional[List[str]] = None, started: Optional[float] = None) -> int:
    import argparse

    started = time.perf_counter() if started is None else started
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--dump", type=Path, default=None,
                        help="write each request's times to this file")
    args = parser.parse_args(argv)

    def log(line: str) -> None:
        print(line, file=sys.stderr, flush=True)

    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          started=started, log=log, dump=args.dump)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    print(f"compiles in window: {result.pop('compiles_in_window')}", flush=True)
    for name, c in result["compared"].items():
        log(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0
