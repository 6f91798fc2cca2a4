"""Health-driven failover + rolling upgrades for Serve.

Pins the failure contract of the proxy→handle→replica path:

- flap damping: one slow/lost health probe never ejects a replica;
  ``PING_FAILURE_THRESHOLD`` consecutive misses do, and the deployment
  recovers with a fresh replica afterwards;
- a replica SIGKILL under load re-routes in-flight unary AND whole
  micro-batches to a fresh replica (clients see 200, never a 5xx);
- transport-typed errors (ConnectionError / injected faults) fail a
  batched call whole — so the proxy re-routes the batch — while user
  exceptions stay isolated per item;
- rolling upgrades warm the new version before draining the old, honor
  the per-deployment ``graceful_shutdown_timeout_s``, let in-flight SSE
  streams finish, and never answer 5xx mid-roll.
"""

import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import cloudpickle
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.common import faults
from ray_tpu.serve.controller import Replica, ServeController, _ItemError
from ray_tpu.serve.deployment import make_deployment


@pytest.fixture(scope="module")
def proxy_addr():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    addr = serve.start(http_port=0, grpc_port=None)
    yield addr
    serve.shutdown()
    ray_tpu.shutdown()


def _url(addr, path):
    return f"http://{addr['http_host']}:{addr['http_port']}{path}"


def _get(addr, path, headers=None, timeout=60):
    req = urllib.request.Request(_url(addr, path), data=b"x",
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _replica_pids(name):
    ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
    _, replicas, *_ = ray_tpu.get(
        [ctrl.get_replicas.remote(name)], timeout=30)[0]
    return ray_tpu.get([r.pid.remote() for r in replicas], timeout=30)


# --------------------------------------------------------------------------
# Flap damping (satellite: controller.py PING_FAILURE_THRESHOLD contract)
# --------------------------------------------------------------------------

def _flag_health_cls():
    """check_health sleeps past the probe timeout while the flag file
    exists — a deterministic 'one slow ping' without killing anything.
    Defined inside a function so cloudpickle ships it BY VALUE to the
    replica worker (a module-level test class pickles by reference,
    which a worker cannot import)."""

    class FlagHealth:
        def __init__(self, flag_path):
            self._flag = flag_path

        def check_health(self):
            if os.path.exists(self._flag):
                time.sleep(0.8)  # > PING_TIMEOUT_S, < 2 probe periods

        def __call__(self, request):
            return "ok"

    return FlagHealth


def _manual_controller():
    """An in-process controller with the background loop frozen, so each
    ``_reconcile_once`` (and thus each health probe round) is explicit
    and the threshold arithmetic is deterministic."""
    ctrl = ServeController()
    ctrl._stop.set()
    ctrl._thread.join(timeout=10)
    ctrl.PING_TIMEOUT_S = 0.5
    return ctrl


def _deploy_direct(ctrl, dep, *init_args):
    ctrl.deploy(dep.name, cloudpickle.dumps(dep),
                cloudpickle.dumps(dep.func_or_class), tuple(init_args), {})


def _wait_ready(ctrl, name, n=1, timeout=30.0):
    """One reconcile to start replicas, then wait for boot by pinging
    directly — NOT via _reconcile_once, whose short-timeout probes would
    count boot time as misses and eject the replica mid-boot."""
    ctrl._reconcile_once()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        _, replicas, *_ = ctrl.get_replicas(name)
        if len(replicas) >= n:
            try:
                ray_tpu.get([r.ping.remote() for r in replicas],
                            timeout=10.0)
                ctrl._ping_failures.clear()  # boot-time misses don't count
                return replicas
            except Exception:  # noqa: BLE001 — still booting
                pass
        time.sleep(0.2)
    raise TimeoutError(f"{name} never became ready")


def test_one_slow_ping_never_ejects(proxy_addr, tmp_path):
    flag = str(tmp_path / "slow_ping_flag")
    ctrl = _manual_controller()
    try:
        dep = make_deployment(_flag_health_cls(), name="flappy",
                              num_replicas=1)
        _deploy_direct(ctrl, dep, flag)
        (replica,) = _wait_ready(ctrl, "flappy")
        rid = replica._actor_id.hex()

        open(flag, "w").close()
        ctrl._reconcile_once()  # probe times out: ONE miss
        _, replicas, *_ = ctrl.get_replicas("flappy")
        assert [r._actor_id.hex() for r in replicas] == [rid], \
            "one slow ping must not eject the replica"
        assert ctrl._ping_failures.get(rid) == 1

        os.remove(flag)
        time.sleep(1.0)  # let the in-flight slow check_health finish
        ctrl._reconcile_once()  # healthy probe clears the miss count
        assert rid not in ctrl._ping_failures
        _, replicas, *_ = ctrl.get_replicas("flappy")
        assert [r._actor_id.hex() for r in replicas] == [rid]
    finally:
        ctrl.shutdown()


def test_threshold_misses_eject_then_recover(proxy_addr):
    ctrl = _manual_controller()
    try:
        dep = make_deployment(_flag_health_cls(), name="flappy2",
                              num_replicas=1)
        _deploy_direct(ctrl, dep, "/nonexistent-flag")
        (replica,) = _wait_ready(ctrl, "flappy2")
        rid = replica._actor_id.hex()

        faults.inject("serve.controller.probe", "always")
        try:
            for i in range(1, ctrl.PING_FAILURE_THRESHOLD):
                ctrl._reconcile_once()
                _, replicas, *_ = ctrl.get_replicas("flappy2")
                assert [r._actor_id.hex() for r in replicas] == [rid], \
                    f"{i} misses must not eject (threshold is " \
                    f"{ctrl.PING_FAILURE_THRESHOLD})"
            ctrl._reconcile_once()  # threshold-th consecutive miss
        finally:
            faults.clear()
        _, replicas, *_ = ctrl.get_replicas("flappy2")
        assert rid not in [r._actor_id.hex() for r in replicas], \
            "threshold consecutive misses must eject the replica"

        # recovery after the flap: a fresh replica serves
        replicas = _wait_ready(ctrl, "flappy2")
        assert len(replicas) == 1
        assert replicas[0]._actor_id.hex() != rid
    finally:
        ctrl.shutdown()


def test_slow_constructor_is_not_a_missed_probe(proxy_addr):
    """A replica whose constructor outlasts PING_FAILURE_THRESHOLD probe
    timeouts (a full-width model landing on its chip) is starting, not
    unhealthy: the GCS has it PENDING_CREATION, no miss is counted and
    the SAME replica serves once it is up."""

    class SlowStart:
        def __init__(self):
            time.sleep(3.0)  # > PING_FAILURE_THRESHOLD * PING_TIMEOUT_S

        def __call__(self, request):
            return "ok"

    ctrl = _manual_controller()
    try:
        dep = make_deployment(SlowStart, name="slowstart", num_replicas=1)
        _deploy_direct(ctrl, dep)
        ctrl._reconcile_once()  # starts it
        _, (replica,), *_ = ctrl.get_replicas("slowstart")
        rid = replica._actor_id.hex()
        probes = []
        probe = ctrl._probe
        ctrl._probe = lambda r: probes.append(probe(r)) or probes[-1]
        deadline = time.monotonic() + 30
        while probes[-1:] != [True] and time.monotonic() < deadline:
            ctrl._reconcile_once()  # one probe of PING_TIMEOUT_S each
            _, replicas, *_ = ctrl.get_replicas("slowstart")
            assert [r._actor_id.hex() for r in replicas] == [rid], \
                "a constructing replica must not be ejected"
            assert rid not in ctrl._ping_failures
        assert probes[-1] is True and set(probes[:-1]) == {None}
        assert len(probes) - 1 > ctrl.PING_FAILURE_THRESHOLD
    finally:
        ctrl.shutdown()


# --------------------------------------------------------------------------
# Whole-batch transport failure semantics (satellite: batch re-route)
# --------------------------------------------------------------------------

class _EchoUser:
    def __call__(self, x):
        if x == "boom":
            raise ValueError("user error")
        return x


def test_batch_transport_error_fails_whole_call_typed():
    """ConnectionError (injected faults included) raises out of
    handle_request_batch — the proxy re-routes the whole batch — while
    user exceptions stay per-item ``_ItemError``."""
    r = Replica(cloudpickle.dumps(_EchoUser), (), {}, max_ongoing=4)
    faults.inject("serve.replica.call", "once")
    try:
        with pytest.raises(ConnectionError):
            r.handle_request_batch(
                "__call__", [((f"i{i}",), {}) for i in range(3)])
    finally:
        faults.clear()
    # same contract for a single-item batch
    faults.inject("serve.replica.call", "once")
    try:
        with pytest.raises(ConnectionError):
            r.handle_request_batch("__call__", [(("solo",), {})])
    finally:
        faults.clear()
    # user exceptions: isolated per item, batchmates unaffected
    out = r.handle_request_batch(
        "__call__", [(("a",), {}), (("boom",), {}), (("b",), {})])
    assert out[0] == "a" and out[2] == "b"
    assert isinstance(out[1], _ItemError)
    assert isinstance(out[1].error, ValueError)


class _StreamUser:
    def stream(self, request):
        yield from range(3)


def test_stream_fault_raises_before_first_item():
    r = Replica(cloudpickle.dumps(_StreamUser), (), {})
    faults.inject("serve.replica.stream", "once")
    try:
        gen = r.handle_request_stream((None,), {})
        with pytest.raises(ConnectionError):
            next(gen)
    finally:
        faults.clear()


def test_proxy_write_fault_is_connection_error():
    import asyncio

    from ray_tpu.serve.proxy import ProxyActor

    class _W:
        def __init__(self):
            self.buf = b""

        def write(self, b):
            self.buf += b

        async def drain(self):
            pass

    w = _W()
    faults.inject("serve.proxy.write", "once")
    try:
        with pytest.raises(ConnectionError):
            asyncio.run(ProxyActor._write_response(
                w, 200, "text/plain", b"payload"))
    finally:
        faults.clear()
    assert w.buf == b"", "the fault must fire before any bytes hit the wire"


# --------------------------------------------------------------------------
# SIGKILL failover through the live proxy
# --------------------------------------------------------------------------

def test_replica_sigkill_under_load_reroutes(proxy_addr):
    """Kill one of two replicas mid-load: every client request still
    answers 200 (unary and coalesced batches retry on the surviving
    replica via the router's mark_dead health view), and the controller
    restores the replica count."""
    @serve.deployment(name="killme", num_replicas=2, max_ongoing_requests=4)
    class Work:
        def __call__(self, request):
            time.sleep(0.15)
            return "ok"

    serve.run(Work.bind())
    try:
        pids = _replica_pids("killme")
        assert len(pids) == 2
        protected = {os.getpid(), os.getppid()}
        victim = next(p for p in pids if p not in protected)

        results, lock = [], threading.Lock()

        def one():
            code, body = _get(proxy_addr, "/killme")
            with lock:
                results.append((code, body))

        threads = [threading.Thread(target=one) for _ in range(16)]
        for t in threads[:8]:
            t.start()
        time.sleep(0.1)  # requests in flight on both replicas
        os.kill(victim, signal.SIGKILL)
        for t in threads[8:]:
            t.start()
        for t in threads:
            t.join(timeout=120)

        assert len(results) == 16, "every request must be answered"
        codes = [c for c, _ in results]
        assert all(c == 200 for c in codes), \
            f"failover must be invisible to clients, got {codes}"

        # controller replaces the corpse: back to 2 replicas, new pid
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                now = _replica_pids("killme")
                if len(now) == 2 and victim not in now:
                    break
            except Exception:  # noqa: BLE001 — mid-replacement
                pass
            time.sleep(0.25)
        else:
            raise AssertionError("replica count never recovered")
    finally:
        serve.delete("killme")


def test_batch_reroutes_whole_batch_on_replica_death(proxy_addr):
    """One replica, slow handler → concurrent arrivals coalesce into a
    batch behind the in-flight call.  SIGKILL the replica mid-batch: the
    whole batch re-routes to the respawned replica; no batchmate fails."""
    @serve.deployment(name="batchy", num_replicas=1, max_ongoing_requests=4,
                      graceful_shutdown_timeout_s=2.0)
    class Work:
        def __call__(self, request):
            time.sleep(0.3)
            return "ok"

    serve.run(Work.bind())
    try:
        (victim,) = _replica_pids("batchy")
        assert victim not in {os.getpid(), os.getppid()}

        results, lock = [], threading.Lock()

        def one():
            code, body = _get(proxy_addr, "/batchy", timeout=120)
            with lock:
                results.append(code)

        threads = [threading.Thread(target=one) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.15)  # first call in flight, the rest queued behind it
        os.kill(victim, signal.SIGKILL)
        for t in threads:
            t.join(timeout=120)

        assert results == [200, 200, 200, 200], \
            f"a dead replica must re-route the whole batch, got {results}"
    finally:
        serve.delete("batchy")


# --------------------------------------------------------------------------
# Rolling upgrades
# --------------------------------------------------------------------------

def test_rolling_upgrade_never_5xx_and_warms_before_drain(proxy_addr):
    @serve.deployment(name="roller", num_replicas=2)
    class V1:
        def __call__(self, request):
            return "v1"

    @serve.deployment(name="roller", num_replicas=2)
    class V2:
        def __init__(self):
            time.sleep(1.0)  # slow warm-up: old must serve meanwhile

        def __call__(self, request):
            return "v2"

    serve.run(V1.bind())
    try:
        assert serve.status()["roller"]["version"] == 1

        stop = threading.Event()
        seen, lock = [], threading.Lock()

        def hammer():
            while not stop.is_set():
                code, body = _get(proxy_addr, "/roller", timeout=30)
                with lock:
                    seen.append((time.monotonic(), code, body))

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        t_deploy = time.monotonic()
        serve.run(V2.bind())  # returns immediately; the roll is async

        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            with lock:
                if any(b == b"v2" for _, _, b in seen):
                    break
            time.sleep(0.1)
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=60)

        assert seen, "hammer produced no samples"
        bad = [(c, b) for _, c, b in seen if c != 200]
        assert not bad, f"mid-roll requests must never see non-200: {bad[:5]}"
        bodies = [b for _, _, b in seen]
        assert b"v1" in bodies and b"v2" in bodies
        # warm-before-drain: v1 kept serving during v2's slow __init__
        v1_after_deploy = [t for t, _, b in seen
                          if b == b"v1" and t > t_deploy]
        assert v1_after_deploy, \
            "old version must keep serving while the new one warms"

        st = serve.status()["roller"]
        assert st["version"] == 2
        # roll completed: replicas report the new version tag
        ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
        _, replicas, *_ = ray_tpu.get(
            [ctrl.get_replicas.remote("roller")], timeout=30)[0]
        versions = {m["version"] for m in ray_tpu.get(
            [r.get_metrics.remote() for r in replicas], timeout=30)}
        assert versions == {2}
    finally:
        serve.delete("roller")


def test_drain_lets_inflight_sse_finish(proxy_addr):
    """Redeploy mid-stream: the draining replica finishes the open SSE
    stream (ongoing > 0 blocks its kill until graceful_shutdown_timeout_s)
    and the client sees every event + [DONE], no error frame."""
    @serve.deployment(name="ssedrain", num_replicas=1,
                      graceful_shutdown_timeout_s=30.0)
    class S1:
        def stream(self, request):
            for i in range(8):
                time.sleep(0.2)
                yield i

    serve.run(S1.bind())
    try:
        events = []
        req = urllib.request.Request(
            _url(proxy_addr, "/ssedrain"), data=b"x",
            headers={"Accept": "text/event-stream"})
        resp = urllib.request.urlopen(req, timeout=120)
        redeployed = False
        for raw in resp:
            line = raw.decode().strip()
            if line.startswith("data: ") or line.startswith("event: "):
                events.append(line)
            if not redeployed and len(events) >= 2:
                serve.run(S1.bind())  # roll while the stream is open
                redeployed = True
            if line == "data: [DONE]":
                break
        resp.close()
        assert redeployed
        datas = [e for e in events if e.startswith("data: ")]
        assert datas[-1] == "data: [DONE]"
        assert [json.loads(e[6:]) for e in datas[:-1]] == list(range(8)), \
            "the draining replica must finish the in-flight stream"
        assert not any(e.startswith("event: error") for e in events)
    finally:
        serve.delete("ssedrain")


def test_graceful_shutdown_timeout_bounds_drain(proxy_addr):
    """A never-ending stream cannot hold a draining replica forever: the
    per-deployment graceful_shutdown_timeout_s (0.5 s here — NOT the old
    hard 10 s) bounds the drain, and the client gets the clean
    `event: error` frame when the replica is finally killed."""
    @serve.deployment(name="ssebound", num_replicas=1,
                      graceful_shutdown_timeout_s=0.5)
    class Endless:
        def stream(self, request):
            i = 0
            while True:
                time.sleep(0.2)
                yield i
                i += 1

    serve.run(Endless.bind())
    try:
        req = urllib.request.Request(
            _url(proxy_addr, "/ssebound"), data=b"x",
            headers={"Accept": "text/event-stream"})
        resp = urllib.request.urlopen(req, timeout=120)
        saw_error = False
        t_redeploy = None
        for raw in resp:
            line = raw.decode().strip()
            if t_redeploy is None and line.startswith("data: "):
                serve.run(Endless.bind())
                t_redeploy = time.monotonic()
            if line.startswith("event: error"):
                saw_error = True
        t_end = time.monotonic()
        resp.close()
        assert t_redeploy is not None
        assert saw_error, "mid-stream kill must surface the error frame"
        # the 0.5 s deployment timeout bounded the drain: stream ended
        # far sooner than the old hard 10 s constant would allow (roll
        # warm-up + drain + kill all inside this window)
        assert t_end - t_redeploy < 8.0, \
            f"drain took {t_end - t_redeploy:.1f}s; per-deployment " \
            "timeout not honored"
    finally:
        serve.delete("ssebound")
