"""The engine loop's named phases, as differences of
``stats()["phases"]`` across the window. A row is ``[count, wall_s,
self_wall_s, timed_self_wall_s, timed_self_cpu_s]``: wall on
``time.monotonic()``, self = without the phases entered inside, the last
two over the turns in which the engine thread's CPU
(``time.thread_time()``) was timed too, one in eight. A program without
phases gives None.

``admit_ms_per_step``: what admission and prefill add to a decode step
(the whole of ``admit``, prefill included, over the decode steps)."""

from _lib import counters

WALL = 1


def delta(a, b, name, column):
    zero = [0, 0.0, 0.0, 0.0, 0.0]
    return b.get(name, zero)[column] - a.get(name, zero)[column]


def read(run, what):
    c = counters(run)
    if c is None or "phases" not in c[0] or "phases" not in c[1]:
        return None
    a, b = c[0]["phases"], c[1]["phases"]
    if what == "admit_ms_per_step":
        steps = c[1]["steps"] - c[0]["steps"]
        return 1e3 * delta(a, b, "admit", WALL) / steps if steps else None
    raise ValueError(f"_phase: no reading called {what!r}")
