"""The readers of the engine loop's spans and counters, on hand-made
events and made-up ``stats()`` pairs (the arithmetic needs no chip)."""

import importlib
import json
import os
import sys

import pytest

from benchmark import run as bench_run

FOLDER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "layer_metrics")
sys.path.insert(0, FOLDER)
requests = importlib.import_module("_requests")

def stats(steps, phases=None, requests=None):
    out = {"steps": steps}
    if phases is not None:
        out["phases"] = phases
    if requests is not None:
        out["requests"] = requests
    return out


def serve_run(open_stats, close_stats, final_stats=None):
    return {"raw": {"open": {"stats": open_stats, "now": 100.0},
                    "close": {"stats": close_stats, "now": 151.0},
                    "final": {"stats": final_stats or close_stats}}}


def row(count, wall, timed_cpu, self_wall=None):
    """A phase's row; CPU timed in one turn of eight, so the timed self
    wall is an eighth of the self wall."""
    self_wall = wall if self_wall is None else self_wall
    return [count, wall, self_wall, self_wall / 8, timed_cpu / 8]


class TestPhaseReaders:
    A = {"grow": row(10, 0.1, 0.1), "admit": row(10, 2.0, 0.3, 0.4),
         "prefill": row(4, 1.6, 0.7), "decode_dispatch": row(10, 0.5, 0.5),
         "sample": row(10, 1.0, 0.9), "logits_fetch": row(10, 9.0, 0.1)}
    B = {"grow": row(110, 0.2, 0.2), "admit": row(110, 6.0, 0.8, 1.4),
         "prefill": row(44, 4.6, 2.2), "decode_dispatch": row(110, 1.5, 1.4),
         "sample": row(110, 9.0, 6.9), "logits_fetch": row(110, 99.0, 0.2)}

    def test_admit_ms_per_step_is_the_whole_of_admit(self):
        run = serve_run(stats(50, self.A), stats(150, self.B))
        assert bench_run.load_reader("step_admit_ms.chat")(run) \
            == pytest.approx(40.0)

    def test_a_reading_the_reader_does_not_have_is_refused_by_name(self):
        """``gil_wait_pct`` went with its entry in PR 56 (it read 32 to 69
        on one tree): an alias that still asks for it does not read 0."""
        run = serve_run(stats(50, self.A), stats(150, self.B))
        phase = bench_run.load_reader("_phase")
        with pytest.raises(ValueError, match="gil_wait_pct"):
            phase(run, what="gil_wait_pct")
        assert phase(run, what="admit_ms_per_step") == pytest.approx(40.0)

    def test_a_program_without_phases_gives_no_number(self):
        run = serve_run(stats(50), stats(150))
        assert bench_run.load_reader("step_admit_ms.chat")(run) is None
        assert bench_run.load_reader("step_admit_ms.chat")(
            {"raw": {"losses": []}}) is None


def record(enqueued, queue=0.01, prefill=0.1, pickup=0.004, decode=5.0):
    first = enqueued + queue + prefill
    return [enqueued, enqueued + queue, first,
            None if pickup is None else first + pickup, first + decode,
            300, 120, 0, "ok"]


class TestRequestReaders:
    def _run(self, recent, finished=None):
        reqs = {"finished": len(recent) if finished is None else finished,
                "recent": recent}
        return serve_run(stats(50), stats(150),
                         stats(160, requests=reqs))

    def test_percentiles_over_the_requests_of_the_window(self, capsys):
        recent = [record(90.0, queue=9.0)]        # before the window
        recent += [record(100.0 + i, queue=0.001 * (i + 1),
                          prefill=0.1 + 0.001 * i) for i in range(20)]
        recent += [record(152.0, queue=9.0)]      # after it
        run = self._run(recent)
        assert bench_run.load_reader("queue_wait_p95_ms")(run) \
            == pytest.approx(19.0)
        assert "over 20 requests" in capsys.readouterr().out
        assert bench_run.load_reader("request_prefill_p50_ms")(run) \
            == pytest.approx(109.0)
        assert bench_run.load_reader("pickup_lag_p95_ms")(run) \
            == pytest.approx(4.0)

    def test_blocking_callers_have_no_pickup(self):
        run = self._run([record(101.0, pickup=None)])
        assert bench_run.load_reader("pickup_lag_p95_ms")(run) is None
        assert bench_run.load_reader("queue_wait_p95_ms")(run) \
            == pytest.approx(10.0)

    def test_an_overflowed_ring_gives_no_number(self):
        recent = [record(101.0 + 0.01 * i) for i in range(512)]
        assert bench_run.load_reader("queue_wait_p95_ms")(
            self._run(recent, finished=600)) is None
        # every record it dropped had finished before the window opened
        recent[0] = record(80.0, decode=5.0)
        assert requests.window_records(
            {"finished": 600, "recent": recent}, 100.0, 151.0) is not None

    def test_a_program_without_records_gives_no_number(self):
        run = serve_run(stats(50), stats(150))
        assert bench_run.load_reader("queue_wait_p95_ms")(run) is None
        assert bench_run.load_reader("queue_wait_p95_ms")(
            {"raw": {"losses": []}}) is None


def _engine_loop_entries():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {w: m["name"] for m in bench["end_to_end"] if m["name"] != "setup_s"
           for w in m["workloads"]}
    return [(m, e2e) for m in bench["per_layer"] if m["name"].startswith((
        "queue_wait", "request_prefill", "pickup_lag", "step_admit"))]


@pytest.mark.parametrize("m, e2e", _engine_loop_entries(),
                         ids=lambda v: v.get("name", ""))
def test_an_engine_loop_metric_has_its_layer_its_cells_metric_and_a_reader(
        m, e2e):
    """Whichever entries read the engine's phases and request records
    (a later PR appends more under a tag of its own)."""
    assert m["layer"] == "engine loop: ray_tpu/serve/llm.py"
    assert m["source"] == "program_counter"
    assert {e2e[w] for w in m["workloads"]} == {m["moves"]}
    assert callable(bench_run.load_reader(m["name"]))


def test_no_entry_splits_the_idle_time_by_span():
    """The reader of the device's idle time by host phase went in PR 41
    with its twenty entries (they read nothing since a turn works one
    step ahead); ``device_idle_pct`` reads what idle time is left."""
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert not [n for n in names if n.startswith(("idle_", "dev_idle_"))]
    assert {"device_idle_pct", "device_idle_pct.chat",
            "overlapped_turn_pct"} <= set(names)
