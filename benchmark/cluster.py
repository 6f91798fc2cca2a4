"""Start and stop discipline of one run (copied from ``chip_smoke.py``,
the original stays for a later PR to fold): the driver never imports
jax, stops every process it started on EVERY way out (an answer, an
exception, a signal, the watchdog), waits until each has ended, and
after a run that gave its answer sees that a fresh process can open the
chips.

What it started is kept as a census, ``SEEN``: ``(pid, start time)`` of
every process found below this one, taken after ``ray_tpu.init()``, once
the replica or the trainer's worker is up, and at the exit. A worker is
its own session (``ray_tpu/raylet/worker_factory.py``, ``os.setsid()``)
and becomes init's child when its factory or raylet dies first, so a
look at the processes below this one at the exit alone does not find it
(ROADMAP D18: two PRs lost their trees to such a process).
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time

SEEN: dict = {}     # (pid, start time) -> command, of this run's processes
SIGNALS = (signal.SIGTERM, signal.SIGHUP, signal.SIGINT)
KILLED_GONE_S = 60.0    # what a process gets to be gone after SIGKILL


def _proc_state(pid):
    """(state, parent pid, start time) of a process from /proc, None if
    it is gone."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return fields[0], int(fields[1]), int(fields[19])
    except (OSError, IndexError, ValueError):   # gone, or going as it is read
        return None


def running(pid) -> bool:
    st = _proc_state(pid)       # a zombie has stopped, it awaits reaping
    return st is not None and st[0] != "Z"


def alive(key) -> bool:
    """Whether the process of the census ``(pid, start time)`` still
    runs: another process that got its pid since is not it."""
    st = _proc_state(key[0])
    return st is not None and st[0] != "Z" and st[2] == key[1]


def descendants() -> dict:
    """{(pid, start time): command} of every running process below this
    one."""
    parent, start = {}, {}
    for entry in filter(str.isdigit, os.listdir("/proc")):
        st = _proc_state(entry)
        if st is not None and st[0] != "Z":
            parent[int(entry)], start[int(entry)] = st[1], st[2]
    found, frontier = {}, {os.getpid()}
    while frontier:
        frontier = {p for p, pp in parent.items() if pp in frontier}
        for pid in frontier:
            try:
                with open(f"/proc/{pid}/cmdline") as f:
                    found[pid, start[pid]] = f.read().replace(
                        "\0", " ")[:100]
            except OSError:
                pass            # gone meanwhile
    return found


def census() -> dict:
    """Add every process now below this one to the run's census, and
    return the whole of it."""
    SEEN.update(descendants())
    return SEEN


def census_in(seconds: float) -> None:
    """Take the census once more in ``seconds``, from a thread: for a
    trainer, whose worker comes up inside a call that returns only when
    the job is done."""
    timer = threading.Timer(seconds, census)
    timer.daemon = True
    timer.start()


def exit_on_signals() -> None:
    """A ``SIGTERM``, ``SIGHUP`` or ``SIGINT`` ends the run as an
    exception does: ``SystemExit`` in the main thread, so that the
    cluster's ``__exit__`` runs (Python's default ends the process with
    the cluster up)."""
    def leave(signum, frame):
        raise SystemExit(128 + signum)

    for sig in SIGNALS:         # one the caller had ignored stays ignored
        if signal.getsignal(sig) is not signal.SIG_IGN:
            signal.signal(sig, leave)


def stop_everything(started: dict, grace_s: float = 30.0) -> list:
    """After the framework's own shutdown, wait for the stragglers (a
    process that held a chip takes seconds to let go of it), kill what
    is still there, and return what would not end. A killed process
    that held a chip can take a quarter of a minute to be gone (the
    raylet allows it 60 s, ``CHIP_HOLDER_EXIT_S``): so does this."""
    started = dict(started)     # the timer's census may add to it meanwhile
    deadline = time.monotonic() + grace_s
    while any(map(alive, started)) and time.monotonic() < deadline:
        time.sleep(0.2)
    for key in filter(alive, started):
        print(f"[cleanup] killing straggler {key[0]}: {started[key]}",
              flush=True)
        try:
            os.kill(key[0], signal.SIGKILL)
        except ProcessLookupError:
            pass                # ended between the look and the kill
    deadline = time.monotonic() + KILLED_GONE_S
    while any(map(alive, started)) and time.monotonic() < deadline:
        time.sleep(0.1)
    for pid, _ in started:      # a child that ended leaves no zombie
        try:                    # for init to reap after this process
            os.waitpid(pid, os.WNOHANG)
        except ChildProcessError:
            pass                # not this process's child, or reaped
    return [key for key in started if alive(key)]


def wait_chips_answer(n: int, timeout_s: float = 180.0) -> None:
    """A fresh process (never this one) opens all ``n`` chips."""
    code = f"import jax; assert len(jax.devices()) == {n}, jax.devices()"
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            probe = subprocess.run([sys.executable, "-c", code], timeout=120,
                                   capture_output=True, text=True)
            if probe.returncode == 0:
                return
            said = probe.stderr[-2000:]
        except subprocess.TimeoutExpired:   # it hung on a chip still held:
            said = "a probe did not return in 120 s"    # one more try
        if time.monotonic() > deadline:
            raise RuntimeError("the chips do not answer after the run:\n"
                               + said)
        time.sleep(3.0)


class Cluster:
    """``with Cluster(chips): ...`` — a ray_tpu cluster that offers at
    least ``chips`` TPUs, and is gone afterwards."""

    def __init__(self, chips: int, serve: bool, pretend: bool = False):
        self.chips, self.serve, self.pretend = chips, serve, pretend

    def __enter__(self):
        import ray_tpu

        try:
            if self.pretend:       # a rehearsal on a CPU: pretend chips
                ray_tpu.init(num_tpus=self.chips)
            else:
                ray_tpu.init()
            census()
            found = ray_tpu.cluster_resources().get("TPU", 0)
            if found < self.chips:
                raise SystemExit(
                    f"no chip: this host offers TPU: {found:g}, the cell "
                    f"needs {self.chips}; nothing was measured")
        except BaseException:   # no __exit__ follows an __enter__ that fails
            try:
                self.stop()
            except Exception as e:  # noqa: BLE001 — the first fault is told
                print(f"[cleanup] {e!r}", flush=True)
            raise
        return self

    def stop(self) -> None:
        """The framework's own shutdown, then the census: on every way
        out, whatever is in flight."""
        import ray_tpu

        # nothing cuts the stopping short; afterwards a signal does again
        # what it did (ignored for good, the probe and a reader that hangs
        # could not be ended by one)
        before = {sig: signal.signal(sig, signal.SIG_IGN) for sig in SIGNALS}
        census()
        try:
            if self.serve:
                from ray_tpu import serve

                serve.shutdown()
        finally:
            try:
                ray_tpu.shutdown()
            finally:
                left = stop_everything(SEEN)
                print(f"[cleanup] {len(SEEN)} processes in the census, "
                      f"{len(left)} still there", flush=True)
                for sig, handler in before.items():
                    signal.signal(sig, handler or signal.SIG_DFL)
        if left:
            raise RuntimeError("processes of this run would not end: "
                               f"{[(k[0], SEEN[k]) for k in left]}")

    def __exit__(self, *exc):
        self.stop()
        if exc[0] is None and not self.pretend:
            # the next run is a new process that needs these chips at once
            wait_chips_answer(self.chips)
        return False
