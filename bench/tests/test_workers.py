"""The workers that serve a query window: every thread runs every request
of the mix before the window opens, and the window waits for its own
requests alone."""
import threading

import pytest

import harness

MIX = {"requests": [
    {"name": "weekly", "weight": 2, "sql": "SELECT {week}", "params": [{"week": "a"}, {"week": "b"}]},
    {"name": "full", "weight": 1, "sql": "SELECT full"},
]}


class Client:
    def __init__(self, fail=None):
        self.calls = []
        self.fail = fail
        self.lock = threading.Lock()

    def query(self, sql):
        with self.lock:
            self.calls.append((threading.current_thread().name, sql))
        if sql == self.fail:
            raise ValueError(sql)
        return sql


def test_every_thread_runs_every_request_before_the_window():
    client, traffic = Client(), harness.Traffic(MIX, 5)
    workers = harness.Workers(client, traffic, 3, False)
    try:
        warm = sorted(client.calls)
        assert warm == sorted((f"bench-worker-{w}", sql) for w in range(3)
                              for sql in ("SELECT a", "SELECT full"))
        client.calls.clear()
        t_open, t_close, requests, hung, late = harness._query_window(workers, 100.0, 0.1)
        assert hung == 0 and len(requests) == 10
        assert len(client.calls) == 10
        assert all(r.answer == traffic.sql(r.request, r.param) for r in requests)
        assert t_close >= t_open + 0.1
    finally:
        workers.close()
    assert not any(t.is_alive() for t in workers.threads)


def test_windows_in_turn_keep_their_own_requests():
    workers = harness.Workers(Client(), harness.Traffic(MIX, 5), 2, False)
    try:
        first = harness._query_window(workers, 50.0, 0.1)[2]
        second = harness._query_window(workers, 100.0, 0.1)[2]
    finally:
        workers.close()
    assert (len(first), len(second)) == (5, 10)
    assert not set(map(id, first)) & set(map(id, second))


def test_a_warm_up_that_fails_stops_the_set_up():
    with pytest.raises(ValueError, match="SELECT full"):
        harness.Workers(Client(fail="SELECT full"), harness.Traffic(MIX, 5), 2, False)
