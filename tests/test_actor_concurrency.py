"""An actor's ``max_concurrency`` is honoured past the worker's default
pool of 64 executor threads: every sync call holds a thread for as long
as it runs, so an LLM replica with 128 decode slots and 256 blocking
callers needs that many, and its controller's health probe needs one
beside them."""

import threading
import time

import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.mark.parametrize("calls", [100])
def test_more_than_64_blocking_calls_run_together_and_a_probe_answers(
        cluster, calls):
    class Gate:
        """``enter`` returns only once ``n`` calls are inside together."""

        def __init__(self, n):
            self._n, self._inside = n, 0
            self._lock = threading.Lock()
            self._all_in = threading.Event()

        def enter(self):
            with self._lock:
                self._inside += 1
                if self._inside >= self._n:
                    self._all_in.set()
            return self._all_in.wait(60.0)

        def ping(self):
            return "alive"

    gate = ray_tpu.remote(Gate).options(max_concurrency=calls).remote(calls)
    refs = [gate.enter.remote() for _ in range(calls - 1)]
    time.sleep(1.0)
    # 99 callers block inside; a probe still gets a thread at once
    t0 = time.monotonic()
    assert ray_tpu.get(gate.ping.remote(), timeout=30) == "alive"
    assert time.monotonic() - t0 < 10.0
    refs.append(gate.enter.remote())        # the hundredth opens the gate
    assert all(ray_tpu.get(refs, timeout=90))
