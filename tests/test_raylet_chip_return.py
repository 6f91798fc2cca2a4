"""Chips go back to the pool only once the process that held them is gone.

A raylet that never starts (no GCS, no sockets), a real process that ignores
SIGTERM the way the TPU runtime holds its device for seconds after one, and
the raylet's own handlers."""

import subprocess
import sys
import time

import pytest

from ray_tpu.common.resources import CPU, TPU, ResourceRequest
from ray_tpu.common.ids import PlacementGroupID, WorkerID
from ray_tpu.raylet.raylet import Bundle, Raylet, WorkerHandle
from ray_tpu.rpc.rpc import IoContext

PG = PlacementGroupID.from_random()
_HOLDER = ("import signal, time; "
           "signal.signal(signal.SIGTERM, signal.SIG_IGN); "
           "print('up', flush=True); time.sleep(120)")


@pytest.fixture
def raylet(tmp_path):
    r = Raylet(("127.0.0.1", 1), resources={CPU: 4.0, TPU: 1.0},
               session_dir=str(tmp_path))
    r._replenish_pool = lambda: None  # no real workers in this test
    procs = []

    def holder(state, lease_id=None, in_pg=False):
        """A live process holding the node's one chip under a lease or as
        an actor, as _grant_lease / h_start_actor leave it."""
        proc = subprocess.Popen([sys.executable, "-c", _HOLDER],
                                stdout=subprocess.PIPE)
        assert proc.stdout.readline().strip() == b"up"  # handler installed
        procs.append(proc)
        request = ResourceRequest({CPU: 1.0, TPU: 1.0})
        w = WorkerHandle(worker_id=WorkerID.from_random(), proc=proc,
                         state=state, request=request,
                         assignment=r.resources.allocate(request))
        assert w.assignment[TPU] == [0]
        if in_pg:   # the chip is the bundle's; the worker sits inside it
            w.pg = (PG, 0)
            r._bundles[PG] = {0: Bundle(
                request=request, assignment=w.assignment, committed=True,
                available=ResourceRequest({}))}
        if lease_id is not None:
            w.lease_id = lease_id
            r._leases[lease_id] = w.worker_id
        r._workers[w.worker_id] = w
        return w

    r.holder = holder
    yield r
    for p in procs:
        p.kill()
        p.wait()


def _free_chips(r):
    return r.resources.snapshot()["available"].get(TPU, 0.0)


def _retire(r, how, w):
    io = IoContext.current()
    if how == "return_worker":
        assert io.run(r.h_return_worker(w.lease_id), timeout=10)
    elif how == "kill_worker":
        assert io.run(r.h_kill_worker(w.worker_id.binary()), timeout=10)
    elif how == "remove_pg":    # a trainer shutting down: kill, then remove
        assert io.run(r.h_kill_worker(w.worker_id.binary()), timeout=10)
        assert io.run(r.h_return_bundles(PG.binary()), timeout=10)
    else:
        io.run(r._on_worker_dead(w, "job finished"), timeout=10)


@pytest.mark.parametrize("how,state,lease", [
    ("return_worker", "LEASED", b"lease-1"),   # a num_tpus task's lease ends
    ("kill_worker", "ACTOR", None),            # kill_actor on a replica
    ("worker_dead", "LEASED", b"lease-2"),     # job reclaim accounts first
    ("remove_pg", "ACTOR", None),              # the chip is a PG bundle's
])
def test_chips_return_after_the_holder_is_gone(raylet, how, state, lease):
    w = raylet.holder(state, lease, in_pg=how == "remove_pg")
    assert _free_chips(raylet) == 0.0
    # the next TPU lease is already queued; record what it finds
    seen = []

    async def grant(lease_id, request, pg_key, runtime_env=None, job_id=None):
        seen.append((w.proc.poll() is not None, _free_chips(raylet)))
        return {"status": "granted"}

    raylet._grant_lease = grant
    fut = IoContext.current().run(_queue_lease(raylet))
    t0 = time.monotonic()
    _retire(raylet, how, w)
    # the handler does not sit on the loop while the holder dies ...
    assert w.worker_id not in raylet._workers
    deadline = time.monotonic() + 10
    while not fut.done() and time.monotonic() < deadline:
        # ... and at no moment are the chips free while it lives
        alive = w.proc.poll() is None
        assert not (alive and _free_chips(raylet) > 0)
        time.sleep(0.005)
    assert fut.done(), "the queued TPU lease was never granted"
    # order: process dead, then chips in the pool, then the next grant
    assert seen == [(True, 1.0)]
    assert w.proc.returncode == -9  # SIGKILL: SIGTERM is ignored for 120 s
    assert time.monotonic() - t0 < 5


async def _queue_lease(r):
    import asyncio

    fut = asyncio.get_running_loop().create_future()
    r._pending_leases.append(
        {"lease_id": b"next", "request": ResourceRequest({TPU: 1.0}),
         "pg": None, "runtime_env": None, "future": fut, "job_id": None,
         "locality": None})
    return fut


def test_a_worker_without_chips_goes_back_to_the_pool(raylet):
    proc = subprocess.Popen([sys.executable, "-c", "import time; "
                             "time.sleep(120)"])
    try:
        request = ResourceRequest({CPU: 1.0})
        w = WorkerHandle(worker_id=WorkerID.from_random(), proc=proc,
                         state="LEASED", request=request,
                         assignment=raylet.resources.allocate(request),
                         lease_id=b"cpu-lease")
        raylet._leases[b"cpu-lease"] = w.worker_id
        raylet._workers[w.worker_id] = w
        assert IoContext.current().run(raylet.h_return_worker(b"cpu-lease"))
        assert w.state == "IDLE" and proc.poll() is None
        assert raylet.resources.snapshot()["available"][CPU] == 4.0
    finally:
        proc.kill()
        proc.wait()
