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
idle = importlib.import_module("_idle_by_span")
requests = importlib.import_module("_requests")

MS = 1_000_000      # the trace's times are nanoseconds


def ev(start_ms, end_ms):
    return (start_ms * MS, (end_ms - start_ms) * MS)


def span(phase, start_ms, end_ms):
    return (phase,) + ev(start_ms, end_ms)


# two decode steps: the device runs 0-40 and 60-100 (two operations
# each), the host fetches, samples, admits (with a prefill dispatch
# inside) and dispatches in the 20 ms between them
OPS = [ev(0, 25), ev(25, 40), ev(60, 90), ev(90, 100)]
SPANS = [span("turn", -2, 53), span("decode_dispatch", -1, 0.5),
         span("logits_fetch", 0.5, 42), span("sample", 42, 52),
         span("turn", 53.5, 112), span("grow", 53.5, 54),
         span("admit", 54, 58), span("prefill", 55, 57),
         span("decode_dispatch", 58, 60.5), span("logits_fetch", 60.5, 101),
         span("sample", 101, 111)]


def no_turn(spans):
    return [s for s in spans if s[0] != "turn"]


class TestIdleBySpan:
    def test_a_gap_is_split_between_the_phases_under_it(self):
        parts, idle_s = idle.attribute(OPS, no_turn(SPANS))
        assert idle_s == pytest.approx(0.020)
        assert parts["fetch"] == pytest.approx(0.002)       # 40 -> 42
        assert parts["sample"] == pytest.approx(0.010)      # 42 -> 52
        # grow 0.5, admit 4 (its prefill's 2 among them), dispatch 2
        assert parts["other_host"] == pytest.approx(0.0065)
        # 52 -> 53.5: between two turns, inside no phase
        assert parts["unattributed"] == pytest.approx(0.0015)
        assert sum(parts.values()) == pytest.approx(idle_s)

    def test_a_gap_under_no_phase_is_unattributed(self):
        parts, idle_s = idle.attribute(OPS, [span("sample", 200, 210)])
        assert parts == {"sample": 0.0, "fetch": 0.0, "other_host": 0.0,
                         "unattributed": pytest.approx(0.020)}
        assert idle_s == pytest.approx(0.020)

    def test_nested_spans_give_the_innermost_phase(self):
        segs = idle.leaf_segments(
            [span("admit", 54, 58), span("prefill", 55, 57)])
        assert [(a / MS, b / MS, p) for a, b, p in segs] == [
            (54, 55, "admit"), (55, 57, "prefill"), (57, 58, "admit")]
        assert idle.part_of("prefill") == idle.part_of("admit") \
            == idle.part_of("idle_wait") == "other_host"
        assert idle.part_of("logits_fetch") == "fetch"

    def test_operations_that_overlap_leave_no_gap(self):
        assert idle.idle_intervals([ev(0, 10), ev(5, 20), ev(20, 30)]) == []
        assert idle.idle_intervals([ev(0, 10), ev(2, 4), ev(12, 13)]) == [
            (10 * MS, 12 * MS)]

    def test_clock_check_passes_on_one_clock_and_fails_on_a_shifted_one(
            self):
        programs = [ev(0, 40), ev(60, 100)]
        by = lambda p, spans: [s[1:] for s in spans if s[0] == p]  # noqa: E731
        share, lag, checked = idle.clock_check(
            programs, by("decode_dispatch", SPANS), by("logits_fetch", SPANS))
        assert (share, checked) == (1.0, 2)
        assert lag == pytest.approx(1.5 * MS)
        shifted = [(p, s + 30 * MS, d) for p, s, d in SPANS]
        share, _, checked = idle.clock_check(
            programs, by("decode_dispatch", shifted),
            by("logits_fetch", shifted))
        assert checked == 1 and share == 0.0

    def _run(self, tmp_path, monkeypatch, spans, device_early_ms=0.0,
             enqueued=None):
        """A run whose trace holds OPS and four decode programs (run ids
        1-4), the device's clock ``device_early_ms`` before the host's."""
        path = tmp_path / "t.xplane.pb"
        path.write_bytes(b"")
        early = device_early_ms * MS
        programs = [ev(0, 40) + (1,), ev(60, 100) + (2,),
                    ev(0, 40) + (3,), ev(60, 100) + (4,)]
        monkeypatch.setattr(idle, "read_events", lambda p: (
            [(s - early, d) for s, d in OPS],
            [(s - early, d, run) for s, d, run in programs], spans,
            enqueued or {}))
        return {"trace": {"xplane": str(path), "window_s": 0.1,
                          "busy_s": 0.08}}

    def test_the_four_parts_sum_to_the_idle_time_of_a_step(
            self, tmp_path, monkeypatch):
        run = self._run(tmp_path, monkeypatch, SPANS)
        got = {p: idle.read(run, p) for p in idle.PARTS}
        # 20 ms idle over the 4 decode programs the capture holds
        assert sum(got.values()) == pytest.approx(5.0)
        assert got["sample"] == pytest.approx(2.5)
        assert idle.covered_share(
            {"turn": [s[1:] for s in SPANS if s[0] == "turn"]},
            SPANS) == pytest.approx(110.5 / 113.5)

    def test_a_shifted_clock_gives_no_number(self, tmp_path, monkeypatch):
        shifted = [(p, s + 30 * MS, d) for p, s, d in SPANS]
        run = self._run(tmp_path, monkeypatch, shifted)
        assert [idle.read(run, p) for p in idle.PARTS] == [None] * 4

    def test_the_runtimes_enqueue_events_give_the_clock_offset(
            self, tmp_path, monkeypatch):
        # the runtime enqueued run 1 at 0 ms and run 2 at 60 ms of the
        # host's clock (3 and 4 ran before the capture saw an enqueue)
        enqueued = {1: 0 * MS, 2: 60 * MS, 9: 500 * MS}
        assert idle.clock_offset(
            [(-1.3 * MS, 40 * MS, 1), (58.7 * MS, 40 * MS, 2),
             (0, 40 * MS, 3)], enqueued) == pytest.approx(1.3 * MS)
        assert idle.clock_offset([(0, 40 * MS, 3)], enqueued) is None
        run = self._run(tmp_path, monkeypatch, SPANS, device_early_ms=1.3,
                        enqueued=enqueued)
        got = {p: idle.read(run, p) for p in idle.PARTS}
        assert got["sample"] == pytest.approx(2.5)
        assert got["fetch"] == pytest.approx(0.5)
        assert sum(got.values()) == pytest.approx(5.0)

    def test_a_program_without_spans_gives_no_number(self, tmp_path,
                                                     monkeypatch):
        run = self._run(tmp_path, monkeypatch, [])
        assert idle.read(run, "sample") is None
        assert idle.read({"trace": {}}, "sample") is None

    def test_read_events_finds_the_spans_of_a_cpu_capture(self, tmp_path):
        jax = pytest.importorskip("jax")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        with jax.profiler.TraceAnnotation("rt.engine.admit", waiting=1):
            with jax.profiler.TraceAnnotation("rt.engine.prefill"):
                pass
        jax.profiler.stop_trace()
        from benchmark import trace_reduce

        ops, programs, spans, enqueued = idle.read_events(
            trace_reduce.find_xplane(str(tmp_path)))
        # a CPU has no device plane and no TPU runtime
        assert ops == [] and programs == [] and enqueued == {}
        assert sorted(s[0] for s in spans) == ["admit", "prefill"]


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

    def test_gil_wait_is_wall_less_cpu_of_the_host_only_phases(self):
        run = serve_run(stats(50, self.A), stats(150, self.B))
        # self wall 0.1 + 1.0 + 1.0 + 8.0, self cpu 0.1 + 0.5 + 0.9 + 6.0:
        # the fetch, which waits for the device, is not among them
        for name in ("engine_gil_wait_pct.chat", "engine_gil_wait_pct.batch"):
            assert bench_run.load_reader(name)(run) == pytest.approx(
                100 * (10.1 - 7.5) / 10.1)

    def test_a_program_without_phases_gives_no_number(self):
        run = serve_run(stats(50), stats(150))
        assert bench_run.load_reader("step_admit_ms.chat")(run) is None
        assert bench_run.load_reader("engine_gil_wait_pct.chat")(run) is None
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


def test_every_new_metric_has_an_entry_and_a_reader():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = [m for m in bench["per_layer"] if m["name"].startswith((
        "queue_wait", "request_prefill", "pickup_lag", "step_admit",
        "engine_gil_wait", "idle_"))]
    assert len(new) == 14
    for m in new:
        assert m["layer"] == "engine loop: ray_tpu/serve/llm.py"
        chat = m["workloads"] == ["serve-chat-steady"]
        assert m["moves"] == ("itl_p95_ms" if chat
                              else "output_tokens_per_s")
        assert callable(bench_run.load_reader(m["name"]))
