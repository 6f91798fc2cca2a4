"""The replica: a thin subclass of the program's ``LLMServer``.

It adds nothing to the served path but a timestamp at entry. Around the
path it makes the weights from the seed, proves the serving programs
against the reference before the engine is built, and lets the driver
start and stop the profiler in this process (only the process that
holds the chip can trace it).
"""

from __future__ import annotations

import os
import time

from ray_tpu.serve.llm import LLMServer


class BenchServer(LLMServer):
    def __init__(self, spec: dict, deployment: dict, seed: int,
                 out_dir: str, rehearse: bool = False):
        t0 = time.monotonic()
        import jax

        from ray_tpu.common.compile_cache import compile_cache_counts
        from ray_tpu.serve.llm import LLMEngine

        from benchmark import model_spec, weights

        self._counts = compile_cache_counts()
        self._spec, self._out_dir = spec, out_dir
        self._rehearse = rehearse
        dev = jax.devices()[0]
        jax_s = time.monotonic() - t0      # import, and the runtime's start
        if dev.platform != "tpu" and not rehearse:
            raise RuntimeError(
                f"no chip: this replica's jax reports platform "
                f"{dev.platform!r} ({dev.device_kind})")
        self._times = {"boot": t0, "jax_s": jax_s}
        params = weights.make(spec, seed)
        jax.block_until_ready(params)
        self._times["weights_s"] = time.monotonic() - t0 - jax_s
        self._seed, self._deployment = seed, deployment
        self.engine = LLMEngine(
            params=params,
            **model_spec.adapter(spec).engine_kwargs(spec, deployment))
        self._ingress = []
        self._tracing = None

    # ------------------------------------------------------ the served path
    def _stamp(self, prompt_or_request):
        from ray_tpu.serve.proxy import Request

        if isinstance(prompt_or_request, Request):
            sent = (prompt_or_request.json() or {}).get("bench_sent")
            if sent is not None:
                self._ingress.append(time.monotonic() - float(sent))

    def stream(self, prompt_or_request, **kwargs):
        # a generator function, as the proxy's push protocol asks
        self._stamp(prompt_or_request)
        yield from super().stream(prompt_or_request, **kwargs)

    # --------------------------------------------------- around the path
    def check(self) -> dict:
        """The serving programs against the reference, on this replica's
        own weights. A call of its own, not part of the constructor: the
        cluster gives a constructor two minutes, and the first run of a
        checkout compiles here."""
        from benchmark import checks

        t0 = time.monotonic()
        self._check = checks.serve_check(
            self.engine.params, self._spec, self._seed, self._deployment,
            engine=self.engine)
        self._times["check_s"] = time.monotonic() - t0
        return self._check

    def bench_info(self) -> dict:
        """Everything the driver wants to know that is not a request."""
        import jax

        dev = jax.devices()[0]
        mem = dev.memory_stats() or {}
        return {"times": dict(self._times), "pid": os.getpid(),
                "stats": self.engine.stats(),
                "compile_requests": dict(self._counts),
                "ingress_s": list(self._ingress),
                "now": time.monotonic(),
                "device": {"platform": dev.platform,
                           "kind": dev.device_kind,
                           "count": len(jax.devices()),
                           "memory_peak_bytes": mem.get("peak_bytes_in_use"),
                           "bytes_limit": mem.get("bytes_limit")}}

    def trace_start(self) -> float:
        import jax

        from benchmark import trace_reduce

        trace_dir = os.path.join(self._out_dir, "trace")
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(
            trace_dir, profiler_options=trace_reduce.profiler_options())
        self._tracing = (trace_dir, time.monotonic())
        return self._tracing[1]

    def trace_stop(self, span_s: float = 0.0) -> dict:
        """Stop the profiler ``span_s`` after it STARTED and reduce the
        trace here, where jax is. This call and ``trace_start`` run on two
        threads of the replica: a start that takes seconds on a busy host
        must not meet its own stop before it has returned."""
        import jax

        from benchmark import trace_reduce

        deadline = time.monotonic() + 120.0
        while self._tracing is None:
            if time.monotonic() > deadline:
                raise RuntimeError("trace_stop: the profiler never started")
            time.sleep(0.01)
        trace_dir, started = self._tracing
        time.sleep(max(0.0, started + span_s - time.monotonic()))
        jax.profiler.stop_trace()
        self._tracing = None
        path = trace_reduce.find_xplane(trace_dir)
        summary = trace_reduce.reduce_planes(trace_reduce.read_xplane(path, self._rehearse))
        summary["layout"] = trace_reduce.list_planes(path)
        summary["xplane"] = path
        return summary
