"""A run that fails leaves nothing behind (ROADMAP D18): the census of
``benchmark/cluster.py`` on processes of its own, and three rehearsals
of a tiny cell through ``run.py`` that are made to fail (a replica that
raises at construction, a ``SIGTERM`` in the window, the raylet killed
first), each of which has to exit non-zero, print no result and leave
no process. The witness is not the census: every process of a run
carries the mark this test puts into the run's environment."""

import json
import os
import signal
import subprocess
import sys
import time
import uuid

import pytest

from benchmark import cluster
from benchmark.tests import test_data_driven as dd

NEVER_UP = '''

def engine_kwargs(spec, deployment):
    raise RuntimeError("made to fail: this replica never comes up")
'''


# ------------------------------------------------ the census, on its own
def _key(pid):
    return pid, cluster._proc_state(pid)[2]


@pytest.fixture
def fresh_census(monkeypatch):
    monkeypatch.setattr(cluster, "SEEN", {})


def test_a_pid_that_another_process_got_since_is_not_the_one_seen():
    proc = subprocess.Popen([sys.executable, "-c",
                             "import time; time.sleep(60)"])
    try:
        key = _key(proc.pid)
        assert cluster.alive(key) and key in cluster.descendants()
        assert not cluster.alive((proc.pid, key[1] + 1))
    finally:
        proc.kill()
        proc.wait()
    assert not cluster.alive(key)


def test_a_process_whose_parent_died_first_stays_in_the_census(
        fresh_census, tmp_path, capfd):
    """The worker's case: a session of its own, its parent gone, so it is
    init's child and no look below this process finds it any more."""
    note = tmp_path / "pid"
    parent = subprocess.Popen([sys.executable, "-c", f'''
import os, subprocess, sys, time
child = subprocess.Popen([sys.executable, "-c",
                          "import time; time.sleep(120)"],
                         start_new_session=True)
open({str(note)!r}, "w").write(str(child.pid))
time.sleep(120)
'''])
    try:
        deadline = time.monotonic() + 30
        while not (note.exists() and note.read_text()):
            assert time.monotonic() < deadline
            time.sleep(0.05)
        orphan = _key(int(note.read_text()))
        assert orphan in cluster.census()
    finally:
        parent.kill()
        parent.wait()
    assert cluster.alive(orphan) and orphan not in cluster.descendants()
    assert orphan in cluster.census()           # the union keeps it
    assert cluster.stop_everything(cluster.SEEN, grace_s=0.0) == []
    assert not cluster.alive(orphan)
    assert f"killing straggler {orphan[0]}" in capfd.readouterr().out


@pytest.mark.parametrize("sig", ["SIGTERM", "SIGHUP", "SIGINT"])
def test_a_signal_ends_the_run_as_an_exception_does(sig):
    code = f"""
import os, signal, sys
sys.path.insert(0, {dd.ROOT!r})
from benchmark import cluster
cluster.exit_on_signals()
try:
    os.kill(os.getpid(), signal.{sig})
    signal.pause()
finally:
    print("the way out was taken")
"""
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 128 + getattr(signal, sig), proc.stderr
    assert proc.stdout == "the way out was taken\n"


# ----------------------------------------------- runs that are made to fail
def _tree(tmp_path, architecture="dense_decoder"):
    bench = dd._copy(tmp_path)
    dd._put(tmp_path, "configs/new-tiny.json",
            dict(dd.TINY, architecture=architecture))
    dd._put(tmp_path, "cells/new-cell.json", {"deployment": dd.DEPLOYMENT})
    dd._put(tmp_path, "traffic/new-mix.json", dd.MIX)
    dd._enter(bench)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))


class Run:
    """``run.py --rehearse`` of the tiny cell, its output in a file, and
    every process that carries its mark."""

    def __init__(self, tmp_path, seconds, **env):
        self.mark = f"BENCH_TEST_MARK={uuid.uuid4().hex}"
        name, value = self.mark.split("=")
        env = dict(os.environ, JAX_PLATFORMS="cpu", **{name: value}, **env)
        env.pop("PYTHONPATH", None)
        self.log = tmp_path / "run.log"
        with open(self.log, "w") as out:
            self.proc = subprocess.Popen(
                [sys.executable, "benchmark/run.py", "--workload",
                 "new-cell", "--seed", "2147483999", "--seconds",
                 str(seconds), "--trace", "0", "--rehearse"],
                cwd=tmp_path, env=env, stdout=out, stderr=subprocess.STDOUT)

    def said(self):
        return self.log.read_text()

    def marked(self):
        """{pid: (parent, command)} of the run's processes that run."""
        found = {}
        for entry in filter(str.isdigit, os.listdir("/proc")):
            try:
                with open(f"/proc/{entry}/environ", "rb") as f:
                    if self.mark.encode() not in f.read().split(b"\0"):
                        continue
                with open(f"/proc/{entry}/cmdline") as f:
                    command = f.read().replace("\0", " ")
            except OSError:
                continue
            state = cluster._proc_state(entry)
            if state is not None and state[0] != "Z" \
                    and int(entry) != os.getpid():
                found[int(entry)] = (state[1], command)
        return found

    def in_its_window(self):
        deadline = time.monotonic() + 300
        while "load starts" not in self.said():
            assert self.proc.poll() is None, self.said()[-3000:]
            assert time.monotonic() < deadline, self.said()[-3000:]
            time.sleep(0.2)
        time.sleep(2.0)         # lead-in over, requests in flight
        assert self.proc.poll() is None, self.said()[-3000:]

    def ended(self, timeout):
        """The exit code; nothing of the run is left, and no result."""
        try:
            code = self.proc.wait(timeout=timeout)
        finally:
            left = self.marked()
            for pid in left:
                os.kill(pid, signal.SIGKILL)    # not this test's to leave
            if self.proc.poll() is None:
                self.proc.wait(timeout=30)
        said = self.said()
        assert left == {}, said[-3000:]
        assert "processes in the census, 0 still there" in said, said[-3000:]
        assert '"correct"' not in said
        return code


def test_a_replica_that_raises_at_construction_leaves_nothing(tmp_path):
    _tree(tmp_path, architecture="never_up")
    with open(os.path.join(dd.BENCH, "architectures",
                           "dense_decoder.py")) as f:
        dd._put(tmp_path, "architectures/never_up.py", f.read() + NEVER_UP)
    run = Run(tmp_path, seconds=3)
    assert run.ended(timeout=600) == 1
    assert "made to fail: this replica never comes up" in run.said()


def test_a_sigterm_in_the_window_leaves_nothing(tmp_path):
    _tree(tmp_path)
    run = Run(tmp_path, seconds=120)
    run.in_its_window()
    assert len(run.marked()) >= 5   # driver, factory, controller, proxy, replica
    run.proc.send_signal(signal.SIGTERM)
    assert run.ended(timeout=300) == 128 + signal.SIGTERM


def test_workers_whose_raylet_was_killed_first_are_stopped(tmp_path):
    """The raylet a process of its own (``RT_control_plane_procs``), and
    killed: its factories and workers are init's children from then on,
    below nothing of the run, and do not end by themselves."""
    _tree(tmp_path)
    run = Run(tmp_path, seconds=120, RT_control_plane_procs="1")
    run.in_its_window()
    before = run.marked()
    raylet = [pid for pid, (parent, command) in before.items()
              if parent == run.proc.pid and "ray_tpu.raylet.raylet" in command]
    assert len(raylet) == 1, before
    below = {pid for pid, (parent, _) in before.items()
             if parent == raylet[0]}
    assert len(below) >= 4, before      # factory, controller, proxy, replica
    os.kill(raylet[0], signal.SIGKILL)
    time.sleep(1.0)
    orphans = {pid for pid, (parent, _) in run.marked().items()
               if pid in below and parent != raylet[0]}
    assert orphans, run.marked()        # no longer below the run
    for pid in orphans:     # and wedged, as in a device call: left to
        os.kill(pid, signal.SIGSTOP)    # itself a worker sees the loss
    run.proc.send_signal(signal.SIGTERM)
    assert run.ended(timeout=300) != 0
    for pid in orphans:
        assert f"[cleanup] killing straggler {pid}" in run.said()
