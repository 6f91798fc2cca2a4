"""Whose time the device's idle time is: the idle seconds of the traced
window, split by the engine loop's phase that the host was in, for each
decode step.

The engine (``ray_tpu/serve/llm.py``) wraps each part of a loop turn in
a ``TraceAnnotation`` named ``rt.engine.<phase>``; in a capture they are
events of the ``/host:CPU`` plane, on the clock of the device plane's
``XLA Ops``. Over the window ``reduce_planes`` uses (first operation's
start to the last one's end), the complement of the union of the
operations is cut by the innermost phase around each instant:
``sample``, ``fetch`` (``logits_fetch``), ``other_host`` (every other
phase but the turn itself), and ``unattributed`` (inside no phase: how
complete the tracing is). The four sum to the window's idle time.

The two planes do NOT share a clock exactly: on a v5e the device's
events lay 1.3 ms before the host's in every step of a trace (PERF.md
section 3). The runtime's own host event ``DoEnqueueProgram`` carries the
``run_id`` of the ``XLA Modules`` event it starts, so the reader measures
the offset (median over the decode programs of enqueue end - program
start: the device is taken to start when the runtime has enqueued the
program) and moves the device's events by it. Then it checks, not
assumes: each run of the decode program must start after the start of a
``decode_dispatch`` span and end before the end of the ``logits_fetch``
span that follows it. Under 99% of them, no number. A program without
the spans gives None. Reads the trace file itself, through jaxlib's
reader (not jax)."""

import bisect
import os
import re
import statistics

PREFIX = "rt.engine."
PARTS = ("sample", "fetch", "other_host", "unattributed")
DEVICE_PLANE, HOST_PLANE = "/device:TPU:0", "/host:CPU"
DECODE_PROGRAM = re.compile(r"^jit_step\b")


def part_of(phase):
    return {"sample": "sample", "logits_fetch": "fetch"}.get(
        phase, "other_host")


def idle_intervals(ops):
    """The stretches between the first operation's start and the last
    one's end that no operation covers. ``ops``: [(start, duration)]."""
    gaps, end = [], None
    for start, dur in sorted(ops):
        if end is not None and start > end:
            gaps.append((end, start))
        end = start + dur if end is None else max(end, start + dur)
    return gaps


def leaf_segments(spans):
    """``spans``: [(phase, start, duration)] of one thread, nested or
    side by side. -> [(start, end, phase)], disjoint and in order, each
    instant under the innermost span around it."""
    out, stack, cursor = [], [], None

    def emit(upto, phase):
        if upto > cursor:
            out.append((cursor, upto, phase))

    for phase, start, dur in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= start:
            end, name = stack.pop()
            emit(end, name)
            cursor = max(cursor, end)
        if stack:
            emit(start, stack[-1][1])
        cursor = start
        stack.append((start + dur, phase))
    while stack:
        end, name = stack.pop()
        emit(end, name)
        cursor = max(cursor, end)
    return out


def attribute(ops, spans):
    """Seconds of idle time for each part, and their total. ``spans``
    without the turn's own span."""
    out = dict.fromkeys(PARTS, 0.0)
    segments = leaf_segments(spans)
    starts = [s[0] for s in segments]
    idle = 0.0
    for lo, hi in idle_intervals(ops):
        idle += hi - lo
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(segments) and segments[i][0] < hi:
            a, b, phase = segments[i]
            cut = min(b, hi) - max(a, lo)
            if cut > 0:
                out[part_of(phase)] += cut
                covered += cut
            i += 1
        out["unattributed"] += (hi - lo) - covered
    assert abs(sum(out.values()) - idle) <= 1e-6 * max(idle, 1.0), (out, idle)
    return {k: v / 1e9 for k, v in out.items()}, idle / 1e9


def clock_check(programs, dispatches, fetches):
    """(share of the decode program's runs that lie inside their own
    dispatch -> fetch spans, median of fetch end - program end in ns,
    runs checked). Runs before the first dispatch span or after the
    last fetch span (the capture's edges) are not counted."""
    if not dispatches or not fetches:
        return 0.0, None, 0
    d_starts = sorted(s for s, _ in dispatches)
    f_spans = sorted((s, s + d) for s, d in fetches)
    f_starts = [s for s, _ in f_spans]
    inside, lags, checked = 0, [], 0
    for start, dur in programs:
        end = start + dur
        if start < d_starts[0] or end > f_spans[-1][1]:
            continue
        checked += 1
        dispatched = d_starts[bisect.bisect_right(d_starts, start) - 1]
        j = bisect.bisect_left(f_starts, dispatched)
        if j < len(f_spans) and end <= f_spans[j][1]:
            inside += 1
            lags.append(f_spans[j][1] - end)
    return (inside / checked if checked else 0.0,
            statistics.median(lags) if lags else None, checked)


def clock_offset(programs, enqueued):
    """Nanoseconds to add to the device's times to put them on the
    host's clock, or None where no program finds its enqueue.
    ``programs``: [(start, duration, run_id)], ``enqueued``: {run_id:
    end of the runtime's DoEnqueueProgram on the host's clock}."""
    seen = [enqueued[run] - start for start, _, run in programs
            if run in enqueued]
    return statistics.median(seen) if seen else None


def read_events(path):
    """(operations, decode program runs, spans, enqueues) of a trace
    file: the first chip's ``XLA Ops`` as [(start, duration)] and its
    ``XLA Modules`` runs of the decode program as [(start, duration,
    run_id)], the host's ``rt.engine.*`` events as [(phase, start,
    duration)] and its ``DoEnqueueProgram`` events as {run_id: end},
    nanoseconds."""
    from jaxlib._profile_data import ProfileData

    ops, programs, spans, enqueued = [], [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(ev.start_ns, ev.duration_ns)
                           for ev in line.events]
                elif line.name == "XLA Modules":
                    programs = [(ev.start_ns, ev.duration_ns,
                                 dict(ev.stats).get("run_id"))
                                for ev in line.events
                                if DECODE_PROGRAM.match(ev.name)]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.append((ev.name[len(PREFIX):], ev.start_ns,
                                      ev.duration_ns))
                    elif ev.name.startswith("DoEnqueueProgram"):
                        run = dict(ev.stats).get("run_id")
                        enqueued[run] = ev.start_ns + ev.duration_ns
    return ops, programs, spans, enqueued


def analyse(run):
    """{part: ms of idle time for each decode step}, or None; once for
    each run, whichever part is asked for first."""
    if "_idle_by_span" in run:
        return run["_idle_by_span"]
    run["_idle_by_span"] = None
    path = (run.get("trace") or {}).get("xplane")
    if not path or not os.path.exists(path):
        return None
    ops, programs, spans, enqueued = read_events(path)
    if not ops or not programs or not spans:
        return None
    offset = clock_offset(programs, enqueued)
    print(f"[idle_by_span] device clock + "
          f"{'?' if offset is None else round(offset / 1e3, 1)} us = host "
          f"clock (enqueue end - program start, median of "
          f"{sum(1 for p in programs if p[2] in enqueued)} decode "
          f"programs)", flush=True)
    ops = [(s + (offset or 0.0), d) for s, d in ops]
    programs = [(s + (offset or 0.0), d) for s, d, _ in programs]
    by_phase = {}
    for phase, start, dur in spans:
        by_phase.setdefault(phase, []).append((start, dur))
    share, lag, checked = clock_check(
        programs, by_phase.get("decode_dispatch", []),
        by_phase.get("logits_fetch", []))
    print(f"[idle_by_span] clock check: {100 * share:.1f}% of {checked} "
          f"decode programs inside their dispatch -> fetch spans, median "
          f"fetch end - program end "
          f"{'-' if lag is None else round(lag / 1e3, 1)} us", flush=True)
    if checked < 3 or share < 0.99:
        return None
    parts, idle_s = attribute(
        ops, [s for s in spans if s[0] != "turn"])
    steps = len(programs)
    trace = run["trace"]
    print(f"[idle_by_span] idle {1e3 * idle_s / steps:.3f} ms a step over "
          f"{steps} steps (trace_reduce: "
          f"{1e3 * (trace['window_s'] - trace['busy_s']) / steps:.3f}); "
          f"turn covered by its phases "
          f"{100 * covered_share(by_phase, spans):.1f}%", flush=True)
    run["_idle_by_span"] = {k: 1e3 * v / steps for k, v in parts.items()}
    return run["_idle_by_span"]


def covered_share(by_phase, spans):
    """Share of the turns' wall time that lies inside a named phase. A
    turn cut by the capture's start or end has no span of its own: its
    phases are left out."""
    turns = sorted((s, s + d) for s, d in by_phase.get("turn", []))
    starts = [a for a, _ in turns]
    covered = 0.0
    for a, b, _ in leaf_segments([s for s in spans if s[0] != "turn"]):
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0:
            covered += max(0.0, min(b, turns[i][1]) - a)
    whole = sum(b - a for a, b in turns)
    return covered / whole if whole else 0.0


def read(run, part):
    parts = analyse(run)
    return None if parts is None else parts[part]
