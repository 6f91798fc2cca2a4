"""Chaos soak: random worker kills while round-3 features are under load
(reference pattern: python/ray/tests/chaos + ResourceKiller actors,
SURVEY §4.4). Bounded runtime; exercises retries, actor restarts, and
streaming-generator replay under real process death."""

import os
import random
import signal
import time

import pytest

import ray_tpu


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


def _proc_status(pid):
    """(ppid, state) from /proc, or None if the pid is gone (exited
    between the pgrep snapshot and this read — a normal race here)."""
    try:
        with open(f"/proc/{pid}/status") as f:
            fields = dict(ln.split(":", 1) for ln in f if ":" in ln)
        return (int(fields["PPid"].strip()),
                fields.get("State", "?").strip()[:1])
    except (OSError, KeyError, ValueError):
        return None


def _cmdline(pid):
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return f.read().replace(b"\0", b" ").decode(errors="replace")
    except OSError:
        return ""


def _ours(pid):
    """Started by THIS pytest process (tests/conftest.py marks everything
    it starts): the tier-1 run has several pytest workers side by side,
    and the chaos loop must not kill the other workers' clusters."""
    try:
        with open(f"/proc/{pid}/environ", "rb") as f:
            return (f"RT_TEST_OWNER={os.environ['RT_TEST_OWNER']}".encode()
                    in f.read().split(b"\0"))
    except OSError:
        return False


def _worker_pids():
    """Pids of THIS run's live worker processes: exec'd workers by cmdline,
    plus factory-forked workers (fork keeps the factory's cmdline, so they
    are identified as CHILDREN of a factory process).

    pgrep's snapshot races process exit: a listed pid may already be
    gone — or worse, REUSED by an unrelated process — by the time we
    kill it.  Every candidate is therefore re-verified against a fresh
    /proc read (cmdline still matches, not a zombie) and the test
    process itself and its ancestors are excluded, so a stale snapshot
    can never aim the SIGKILL at the pytest run or an innocent pid."""
    import subprocess

    def pgrep(pat):
        out = subprocess.run(["pgrep", "-f", pat],
                             capture_output=True, text=True).stdout.split()
        return [int(p) for p in out if p.isdigit()]

    protected = {os.getpid(), os.getppid()}
    pids = []
    for cand in pgrep("ray_tpu.core_worker.worker_main"):
        st = _proc_status(cand)
        if (cand not in protected and st is not None and st[1] != "Z"
                and "ray_tpu.core_worker.worker_main" in _cmdline(cand)
                and _ours(cand)):
            pids.append(cand)
    factories = set(pgrep("ray_tpu.raylet.worker_factory"))
    for cand in factories:
        st = _proc_status(cand)
        if st is None or st[1] == "Z" or cand in protected:
            continue
        if "ray_tpu.raylet.worker_factory" not in _cmdline(cand):
            continue  # pid reused since the pgrep snapshot
        if st[0] in factories and _ours(cand):  # a forked worker, not
            pids.append(cand)                   # the factory itself
    return pids


def test_tasks_survive_random_worker_kills(rt):
    """A stream of retriable tasks completes correctly while a chaos loop
    SIGKILLs random worker processes."""
    @ray_tpu.remote(max_retries=5)
    def work(i):
        time.sleep(0.02)
        return i * 3

    rng = random.Random(0)
    stop = time.monotonic() + 20.0
    refs = []
    submitted = 0
    kills = 0
    while time.monotonic() < stop:
        refs.extend(work.remote(submitted + j) for j in range(10))
        submitted += 10
        if rng.random() < 0.3:
            pids = _worker_pids()
            if pids:
                victim = rng.choice(pids)
                try:
                    os.kill(victim, signal.SIGKILL)
                    kills += 1
                except OSError:
                    pass
        time.sleep(0.2)
        if submitted >= 300:
            break
    vals = ray_tpu.get(refs, timeout=300)
    assert vals == [i * 3 for i in range(submitted)]
    assert kills >= 1, "chaos loop never found a worker to kill"


def test_streaming_generator_survives_kills(rt):
    """Streaming tasks replay through worker death: all items arrive
    exactly once even when the producer's worker is killed mid-stream."""
    @ray_tpu.remote(num_returns="streaming", max_retries=5)
    def gen(n):
        for i in range(n):
            time.sleep(0.02)
            yield i

    g = gen.remote(40)
    got = []
    killed = False
    for k, ref in enumerate(g):
        got.append(ray_tpu.get(ref))
        if k == 5 and not killed:
            for pid in _worker_pids():
                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass
            killed = True
    assert got == list(range(40))
    assert killed


def test_restartable_actor_through_kills(rt):
    """An actor with max_restarts keeps serving (state resets, calls
    resume) across a SIGKILL of its worker."""
    @ray_tpu.remote(max_restarts=3)
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

        def pid(self):
            return os.getpid()

    c = Counter.remote()
    assert ray_tpu.get(c.incr.remote(), timeout=60) == 1
    pid = ray_tpu.get(c.pid.remote(), timeout=60)
    os.kill(pid, signal.SIGKILL)
    deadline = time.monotonic() + 90
    val = None
    while time.monotonic() < deadline:
        try:
            val = ray_tpu.get(c.incr.remote(), timeout=30)
            break
        except Exception:
            time.sleep(0.5)
    assert val == 1, f"restarted actor should reset state, got {val}"
    assert ray_tpu.get(c.pid.remote(), timeout=30) != pid
