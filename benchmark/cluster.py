"""Start and stop discipline of one run (copied from ``chip_smoke.py``,
the original stays for a later PR to fold): the driver never imports
jax, stops every process it started, waits until each has ended, and
then sees that a fresh process can open the chips.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time


def _proc_state(pid):
    """(state, parent pid) of a process from /proc, None if it is gone."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
    except OSError:
        return None
    return fields[0], int(fields[1])


def running(pid) -> bool:
    st = _proc_state(pid)       # a zombie has stopped, it awaits reaping
    return st is not None and st[0] != "Z"


def descendants() -> dict:
    """{pid: command} of every running process below this one."""
    parent = {}
    for entry in filter(str.isdigit, os.listdir("/proc")):
        st = _proc_state(entry)
        if st is not None and st[0] != "Z":
            parent[int(entry)] = st[1]
    found, frontier = {}, {os.getpid()}
    while frontier:
        frontier = {p for p, pp in parent.items() if pp in frontier}
        for pid in frontier:
            try:
                with open(f"/proc/{pid}/cmdline") as f:
                    found[pid] = f.read().replace("\0", " ")[:100]
            except OSError:
                pass            # gone meanwhile
    return found


def stop_everything(started: dict, grace_s: float = 30.0) -> None:
    """After the framework's own shutdown, wait for the stragglers (a
    process that held a chip takes seconds to let go of it) and kill
    what is still there."""
    deadline = time.monotonic() + grace_s
    while any(map(running, started)) and time.monotonic() < deadline:
        time.sleep(0.2)
    for pid in filter(running, started):
        print(f"[cleanup] killing straggler {pid}: {started[pid]}",
              flush=True)
        os.kill(pid, signal.SIGKILL)
    deadline = time.monotonic() + 10.0
    while any(map(running, started)) and time.monotonic() < deadline:
        time.sleep(0.1)


def wait_chips_answer(n: int, timeout_s: float = 180.0) -> None:
    """A fresh process (never this one) opens all ``n`` chips."""
    code = f"import jax; assert len(jax.devices()) == {n}, jax.devices()"
    deadline = time.monotonic() + timeout_s
    while True:
        probe = subprocess.run([sys.executable, "-c", code], timeout=120,
                               capture_output=True, text=True)
        if probe.returncode == 0:
            return
        if time.monotonic() > deadline:
            raise RuntimeError("the chips do not answer after the run:\n"
                               + probe.stderr[-2000:])
        time.sleep(3.0)


class Cluster:
    """``with Cluster(chips): ...`` — a ray_tpu cluster that offers at
    least ``chips`` TPUs, and is gone afterwards."""

    def __init__(self, chips: int, serve: bool, pretend: bool = False):
        self.chips, self.serve, self.pretend = chips, serve, pretend

    def __enter__(self):
        import ray_tpu

        if self.pretend:       # a rehearsal on a CPU: pretend chips
            ray_tpu.init(num_tpus=self.chips)
        else:
            ray_tpu.init()
        found = ray_tpu.cluster_resources().get("TPU", 0)
        if found < self.chips:
            self.__exit__(None, None, None)
            raise SystemExit(
                f"no chip: this host offers TPU: {found:g}, the cell needs "
                f"{self.chips}; nothing was measured")
        return self

    def __exit__(self, *exc):
        import ray_tpu

        started = descendants()
        try:
            if self.serve:
                from ray_tpu import serve

                serve.shutdown()
        finally:
            ray_tpu.shutdown()
            stop_everything(started)
        if exc[0] is None and not self.pretend:
            # the next run is a new process that needs these chips at once
            wait_chips_answer(self.chips)
        return False
