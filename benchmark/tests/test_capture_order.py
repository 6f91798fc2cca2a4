"""A capture's stop and its start run on two threads of the replica
(``max_concurrency`` = ``max_ongoing_requests``): a stop that is handed
out while the start has not returned waits for it, and the capture then
lasts the span asked for from the moment the profiler ran."""

import threading
import time

import pytest

from benchmark import trace_reduce
from benchmark.worker_serve import BenchServer


@pytest.fixture
def replica(monkeypatch, tmp_path):
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: (time.sleep(0.4),
                                         calls.append(("start",
                                                       time.monotonic()))))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", time.monotonic())))
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(trace_reduce, "read_xplane", lambda p, r: {})
    monkeypatch.setattr(trace_reduce, "reduce_planes", lambda planes: {})
    monkeypatch.setattr(trace_reduce, "list_planes", lambda p: [])
    server = object.__new__(BenchServer)
    server._out_dir, server._rehearse = str(tmp_path), True
    server._tracing = None
    return server, calls


def test_a_stop_handed_out_before_the_start_returned_waits_for_it(replica):
    server, calls = replica
    start = threading.Thread(target=server.trace_start)
    start.start()
    time.sleep(0.05)            # the start is inside the profiler's call
    assert server._tracing is None
    summary = server.trace_stop(0.3)
    start.join()
    assert [name for name, _ in calls] == ["start", "stop"]
    assert calls[1][1] - calls[0][1] >= 0.3
    assert summary["xplane"] == "x.pb" and server._tracing is None


def test_a_stop_after_its_span_stops_at_once(replica):
    server, calls = replica
    server.trace_start()
    time.sleep(0.2)
    t0 = time.monotonic()
    server.trace_stop(0.1)
    assert time.monotonic() - t0 < 0.1
