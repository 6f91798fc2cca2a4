"""Tracing spans: local nesting, cross-process propagation through task
submission, chrome-trace export (reference: ray.util.tracing OTel
task-span wrappers)."""

import pytest

import ray_tpu
from ray_tpu.util import tracing


@pytest.fixture(autouse=True)
def _tracing_on():
    tracing.enable(True)
    tracing.recorder().drain()
    yield
    tracing.enable(False)


class TestSpansLocal:
    def test_nesting_and_recording(self):
        with tracing.span("outer", attributes={"k": 1}) as outer:
            assert tracing.current_span() is outer
            with tracing.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
        spans = tracing.recorder().snapshot()
        names = [s.name for s in spans]
        assert names == ["inner", "outer"]  # finish order
        assert all(s.t1 >= s.t0 for s in spans)

    def test_error_status(self):
        with pytest.raises(ValueError):
            with tracing.span("boom"):
                raise ValueError("x")
        assert tracing.recorder().snapshot()[-1].status == "ERROR: ValueError"

    def test_disabled_is_noop(self):
        tracing.enable(False)
        with tracing.span("ghost") as s:
            assert s is None
        assert tracing.recorder().snapshot() == []

    def test_chrome_export(self):
        with tracing.span("evt", attributes={"a": "b"}):
            pass
        events = tracing.spans_to_chrome_events(
            tracing.recorder().snapshot())
        assert events[0]["ph"] == "X" and events[0]["name"] == "evt"
        assert events[0]["args"]["a"] == "b"


class TestCrossProcess:
    def test_task_span_parents_to_driver_span(self):
        ray_tpu.init(num_cpus=2, num_tpus=0)
        try:
            @ray_tpu.remote
            def traced():
                from ray_tpu.util import tracing as t

                span = t.current_span()
                # the execution span exists and belongs to the DRIVER's
                # trace (context traveled inside the task spec)
                return (span.trace_id, span.parent_id) if span else None

            with tracing.span("driver-root") as root:
                out = ray_tpu.get(traced.remote(), timeout=60)
            assert out is not None
            trace_id, parent_id = out
            assert trace_id == root.trace_id
            assert parent_id is not None
        finally:
            ray_tpu.shutdown()


class TestRequestSpans:
    def test_request_phases_parent_to_the_callers_span(self):
        """A request's queue, prefill and decode show in the timeline
        under the span that carried it, from the engine's own records."""
        from ray_tpu.models import llama
        from ray_tpu.serve.llm import LLMEngine

        eng = LLMEngine(config=llama.CONFIGS["debug"], num_slots=2,
                        max_seq=64)
        try:
            with tracing.span("task::stream") as root:
                eng.generate([5, 17, 99], max_tokens=4)
            tracing.enable(False)
            eng.generate([1, 2], max_tokens=2)      # no caller, no spans
        finally:
            eng.shutdown()
        spans = {s.name: s for s in tracing.recorder().snapshot()}
        assert set(spans) == {"task::stream", "llm.queue", "llm.prefill",
                              "llm.decode"}
        parts = [spans[n] for n in ("llm.queue", "llm.prefill",
                                    "llm.decode")]
        for s in parts:
            assert s.trace_id == root.trace_id
            assert s.parent_id == root.span_id
            assert root.t0 <= s.t0 <= s.t1 <= root.t1 + 0.05
            assert s.attributes["output_len"] == 4
        assert parts[0].t1 == parts[1].t0 and parts[1].t1 == parts[2].t0
        events = tracing.spans_to_chrome_events(parts)
        assert {e["tid"] for e in events} == {root.trace_id[:8]}
