"""The account of an engine turn from a capture's two planes on ONE
clock: whose time the device's idle time is, by the engine phase the
host was in and by the program the device had run last.

The engine (``ray_tpu/serve/llm.py``) wraps each part of a loop turn in
a ``TraceAnnotation`` named ``rt.engine.<phase>``; in a capture they are
events of the ``/host:CPU`` plane. The device's ``XLA Ops`` and ``XLA
Modules`` lie on the first chip's plane, and the runtime's own host
event ``DoEnqueueProgram`` carries the ``run_id`` of the ``XLA Modules``
event it starts. This file reads all four through jaxlib's
``ProfileData`` (not jax) and joins them:

**The offset.** The two planes do NOT share a clock exactly: on a v5e
the device's events lie 1.3-1.6 ms before the host's. The engine works
one step ahead, so most programs are enqueued while the one before them
runs and start when it ends, whenever they were enqueued. Only a run
that found the device EMPTY (it closes an idle gap) starts when its
enqueue is done: there ``program start = enqueue end + launch``, and the
smallest of those differences are the offset (:func:`clock_offset`: a
low percentile over the gap-closing runs, None under 3 of them). It is
measured, printed and applied, never assumed 0.

**The check, before any number.** Each run of the decode program is
paired, through its ``run_id``'s enqueue, with the ``decode_dispatch``
(or ``block_dispatch``) span that was open when the enqueue began, or,
since the runtime enqueues on a thread of its own, the last one begun
before it. On the host's clock the run must END before the end of the
``logits_fetch`` / ``block_fetch`` span whose ``step`` EQUALS that
dispatch's ``step`` (step N+1 is dispatched before step N is fetched,
so it is not the first fetch after the dispatch), and no program may
start before its enqueue began. Under 99% of the paired runs (or fewer
than 3 of them): every reading is None and the line says why.

**The cut.** The idle intervals of the capture (the complement of the
union of the operations between the first one's start and the last
one's end, as ``trace_reduce.reduce_planes`` has them) are cut by the
innermost engine phase around each instant (``turn`` where no phase
inside it is open, ``unattributed`` outside every turn: how complete
the spans are) and labelled by the program that ran last before the gap
opened: ``prefill`` where that was a ``jit_prefill`` or the ids program
that follows one (the turn was blocked on an admission and the device
then waits for the host to seat it and dispatch again), else the
program's name, ``in <name>`` for a gap between two operations of one
run.

``read(run, what)``: ``after_prefill``, ``in_turn`` (the rest of the
idle time under a phase) or ``unattributed``, in ms a decode step of
the capture. The three sum to the idle time. Printed once a run,
``[turn_account] ...``: the offset, the check, the table phase x program
before, the ten longest gaps each with its phase and step, and the
identity ``step programs + prefills + other programs + idle = captured
span / steps``."""

import bisect
import heapq
import os
import re

PREFIX = "rt.engine."
DEVICE_PLANE, HOST_PLANE = "/device:TPU:0", "/host:CPU"
STEP_PROGRAM = re.compile(r"^jit_(block_)?step$")
PREFILL_PROGRAM = re.compile(r"^jit_prefill")
IDS_PROGRAM = re.compile(r"^jit_\w+_ids$")
DISPATCHES = ("decode_dispatch", "block_dispatch")
FETCHES = ("logits_fetch", "block_fetch")
UNATTRIBUTED, AFTER_PREFILL = "unattributed", "prefill"
# a run "found the device empty" where at least this much idle time lies
# before its first operation (ns): two queued programs follow each other
# within a few microseconds
EMPTY_NS = 20e3
# what the check forgives (ns): the offset is the enqueue's END less a
# launch of unknown length, and the enqueue's own event is 45-60 us long
SLACK_NS = 60e3
OFFSET_PCT = 10


def program_name(event_name):
    return event_name.split("(")[0]


def merged(ops):
    """The union of ``ops`` [(start, duration)] as disjoint [lo, hi)
    intervals in order."""
    out = []
    for start, dur in sorted(ops):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], start + dur)
        else:
            out.append([start, start + dur])
    return out


def idle_intervals(ops):
    """The stretches between the first operation's start and the last
    one's end that no operation covers. ``ops``: [(start, duration)]."""
    busy = merged(ops)
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])]


def leaf_segments(spans):
    """``spans``: [(label, start, duration)] of one thread, nested or
    side by side. -> [(start, end, label)], disjoint and in order, each
    instant under the innermost span around it."""
    out, stack, cursor = [], [], None

    def emit(upto, label):
        if upto > cursor:
            out.append((cursor, upto, label))

    for label, start, dur in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= start:
            end, name = stack.pop()
            emit(end, name)
            cursor = max(cursor, end)
        if stack:
            emit(start, stack[-1][1])
        cursor = start
        stack.append((start + dur, label))
    while stack:
        end, name = stack.pop()
        emit(end, name)
        cursor = max(cursor, end)
    return out


def percentile(values, pct):
    values = sorted(values)
    return values[min(len(values) - 1, int(len(values) * pct / 100.0))]


def gap_closers(programs, gaps, empty_ns=EMPTY_NS):
    """The runs that found the device empty: a program whose start lies
    in (or at the end of) an idle gap of at least ``empty_ns``.
    ``programs``: [(name, start, duration, run_id)] in start order."""
    starts = [p[1] for p in programs]
    out = []
    for lo, hi in gaps:
        if hi - lo < empty_ns:
            continue
        i = bisect.bisect_right(starts, hi) - 1
        # the module's event may open a little before its first operation
        if i >= 0 and programs[i][1] >= lo:
            out.append(programs[i])
    return out


def clock_offset(programs, enqueued, gaps):
    """(nanoseconds to add to the device's times to put them on the
    host's clock, the runs it was taken from); (None, n) under 3 runs.
    Over the runs that closed an idle gap, ``program start - enqueue
    end`` is ``launch - offset``: its low end is the offset."""
    seen = [start - enqueued[run][1]
            for _, start, _, run in gap_closers(programs, gaps)
            if run in enqueued]
    if len(seen) < 3:
        return None, len(seen)
    return -percentile(seen, OFFSET_PCT), len(seen)


def offset_from_all_runs(programs, enqueued):
    """The estimator this file does NOT use (PR 25's: the median over
    every decode run of enqueue end - program start), printed beside the
    measured one: a run enqueued behind another starts when that one
    ends, so this reads the offset LESS half a step or so."""
    seen = sorted(enqueued[run][1] - start
                  for name, start, _, run in programs
                  if run in enqueued and STEP_PROGRAM.match(name))
    return seen[len(seen) // 2] if seen else None


def clock_check(programs, enqueued, spans, offset):
    """(decode runs that passed, decode runs paired, those whose enqueue
    lay inside its dispatch span, runs of any program that have an
    enqueue, {fault: runs}). ``programs`` on the DEVICE's clock,
    ``offset`` puts them on the host's."""
    dispatches = sorted((s, s + d, step) for name, s, d, step in spans
                        if name in DISPATCHES and step is not None)
    d_starts = [d[0] for d in dispatches]
    fetch_end = {step: s + d for name, s, d, step in spans
                 if name in FETCHES and step is not None}
    passed = paired = enclosed = joined = 0
    why = {}

    def fault(reason):
        why[reason] = why.get(reason, 0) + 1

    for name, start, dur, run in programs:
        if run not in enqueued:
            continue
        joined += 1
        enq_lo, _ = enqueued[run]
        early = start + offset < enq_lo - SLACK_NS
        if early:
            fault("began before their enqueue")
        if not STEP_PROGRAM.match(name):
            continue
        i = bisect.bisect_right(d_starts, enq_lo) - 1
        if i < 0 or dispatches[i][2] not in fetch_end:
            continue        # the capture's edge: no span to pair it with
        paired += 1
        enclosed += enq_lo <= dispatches[i][1]
        if start + dur + offset > fetch_end[dispatches[i][2]] + SLACK_NS:
            fault("decode runs ended after their step's fetch")
        elif not early:
            passed += 1
    return passed, paired, enclosed, joined, why


def label_before(programs, starts, lo, hi):
    """What the device had run last when the gap ``[lo, hi)`` opened:
    ``in <name>`` where the gap closes inside that run too (an event's
    times are rounded to the nanosecond, so a run's last operation may
    end a few before the run does: where the gap opens says nothing)."""
    i = bisect.bisect_right(starts, lo) - 1
    if i < 0:
        return "nothing"
    name, start, dur, _ = programs[i]
    if hi <= start + dur:
        return "in " + name
    if PREFILL_PROGRAM.match(name) or (
            IDS_PROGRAM.match(name) and i > 0
            and PREFILL_PROGRAM.match(programs[i - 1][0])):
        return AFTER_PREFILL
    return name


def cut(gaps, segments, programs, offset):
    """({(phase, program before): ns}, [(ns, phase, step, program
    before)] of every gap, by its longest part). ``gaps`` and
    ``programs`` on the device's clock (a gap is told from the program
    before it there, where both are exact), ``segments`` from
    :func:`leaf_segments` over labels ``(phase, step)`` on the host's,
    ``offset`` between the two."""
    seg_starts = [s[0] for s in segments]
    prog_starts = [p[1] for p in programs]
    table, longest = {}, []
    for lo, hi in gaps:
        before = label_before(programs, prog_starts, lo, hi)
        lo, hi = lo + offset, hi + offset
        covered, parts = 0.0, {}
        i = max(0, bisect.bisect_right(seg_starts, lo) - 1)
        while i < len(segments) and segments[i][0] < hi:
            a, b, label = segments[i]
            piece = min(b, hi) - max(a, lo)
            if piece > 0:
                parts[label] = parts.get(label, 0.0) + piece
                covered += piece
            i += 1
        if hi - lo - covered > 0:
            parts[(UNATTRIBUTED, None)] = hi - lo - covered
        for (phase, _), ns in parts.items():
            table[(phase, before)] = table.get((phase, before), 0.0) + ns
        (phase, step), _ = max(parts.items(), key=lambda kv: kv[1])
        longest.append((hi - lo, phase, step, before))
    return table, longest


def read_events(path):
    """A capture's file as plain lists, nanoseconds: the first chip's
    ``XLA Ops`` as [(start, duration)] and ``XLA Modules`` as [(program,
    start, duration, run_id)]; the host's ``rt.engine.*`` events as
    [(phase, start, duration, step)] (``step``: the event's ``step``
    stat, a turn's ``step_num``, else None) and its ``DoEnqueueProgram``
    events as {run_id: (start, end)}."""
    from jaxlib._profile_data import ProfileData

    ops, programs, spans, enqueued = [], [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(ev.start_ns, ev.duration_ns)
                           for ev in line.events]
                elif line.name == "XLA Modules":
                    programs = [(program_name(ev.name), ev.start_ns,
                                 ev.duration_ns,
                                 dict(ev.stats).get("run_id"))
                                for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        stats = dict(ev.stats)
                        spans.append((ev.name[len(PREFIX):], ev.start_ns,
                                      ev.duration_ns,
                                      stats.get("step",
                                                stats.get("step_num"))))
                    elif ev.name.startswith("DoEnqueueProgram"):
                        run = dict(ev.stats).get("run_id")
                        enqueued[run] = (ev.start_ns,
                                         ev.start_ns + ev.duration_ns)
    return {"ops": ops, "programs": programs, "spans": spans,
            "enqueued": enqueued}


def say(text):
    print(f"[turn_account] {text}", flush=True)


def account(events):
    """{what: ms a decode step} of :func:`read`'s three readings, with
    the printed account; None (and a line that says why) where the
    capture lacks a plane, the spans or the enqueues, or the clocks
    cannot be joined."""
    ops = events["ops"]
    programs = sorted(events["programs"], key=lambda p: p[1])
    spans, enqueued = events["spans"], events["enqueued"]
    steps = sum(1 for p in programs if STEP_PROGRAM.match(p[0]))
    if not ops or not steps:
        return say("no reading: the capture has no operation or no run of "
                   "the decode program on " + DEVICE_PLANE)
    if not spans:
        return say("no reading: the capture has no rt.engine.* span (a "
                   "program without the engine's phases)")
    if not enqueued:
        return say("no reading: the capture has no DoEnqueueProgram event "
                   "to join the clocks by")
    gaps = idle_intervals(ops)
    offset, n = clock_offset(programs, enqueued, gaps)
    if offset is None:
        return say(f"no reading: {n} runs found the device empty and had "
                   "an enqueue, the offset takes 3")
    naive = offset_from_all_runs(programs, enqueued)
    say(f"device clock + {offset / 1e3:.1f} us = host clock ({n} runs that "
        f"found the device empty; the median over every decode run would "
        f"say {naive / 1e3:.1f} us)" if naive is not None else
        f"device clock + {offset / 1e3:.1f} us = host clock ({n} runs)")
    passed, paired, enclosed, joined, why = clock_check(
        programs, enqueued, spans, offset)
    share = passed / paired if paired else 0.0
    early = why.get("began before their enqueue", 0)
    say(f"clock check: {100 * share:.1f}% of {paired} paired decode runs "
        f"began after their enqueue and ended before the fetch of their "
        f"own step ({enclosed} enqueues inside their dispatch span; "
        f"{joined} runs of any program with an enqueue)"
        + "".join(f"; {v} {k}" for k, v in sorted(why.items())))
    if paired < 3 or share < 0.99 or early > 0.01 * joined:
        return say("no reading: the check failed, the planes are not on "
                   "one clock")
    segments = leaf_segments([((name, step), s, d)
                              for name, s, d, step in spans])
    table, every = cut(gaps, segments, programs, offset)
    per_step = 1e-6 / steps           # ns -> ms a decode step
    idle = sum(table.values())
    out = {"after_prefill": 0.0, "in_turn": 0.0, "unattributed": 0.0}
    for (phase, before), ns in table.items():
        key = ("after_prefill" if before == AFTER_PREFILL else
               "unattributed" if phase == UNATTRIBUTED else "in_turn")
        out[key] += ns * per_step
    lo, hi = min(s for s, _ in ops), max(s + d for s, d in ops)
    span = hi - lo
    say(f"idle {idle * per_step:.3f} ms a step ({100 * idle / span:.2f}% "
        f"of the captured {span / 1e9:.3f} s, {steps} decode steps): "
        f"after a prefill {out['after_prefill']:.3f}, under a phase "
        f"{out['in_turn']:.3f}, unattributed {out['unattributed']:.3f}")
    befores = sorted({b for _, b in table},
                     key=lambda b: -sum(v for (_, bb), v in table.items()
                                        if bb == b))[:6]
    say("ms a step, phase x program before | " + " | ".join(
        ["phase"] + befores))
    for phase in sorted({p for p, _ in table},
                        key=lambda p: -sum(v for (pp, _), v in table.items()
                                           if pp == p)):
        say(f"  {phase} | " + " | ".join(
            f"{table.get((phase, b), 0.0) * per_step:.3f}" for b in befores))
    for ns, phase, step, before in heapq.nlargest(
            10, every, key=lambda gap: gap[0]):
        say(f"  gap {ns / 1e6:.3f} ms under {phase} (step {step}) after "
            f"{before}")
    by_class = {"step": 0.0, "prefill": 0.0, "other": 0.0}
    for name, s, d, _ in programs:
        if s >= lo and s + d <= hi:
            by_class["step" if STEP_PROGRAM.match(name) else
                     "prefill" if PREFILL_PROGRAM.match(name)
                     else "other"] += d
    inside = sum(v for (_, b), v in table.items() if b.startswith("in "))
    total = sum(by_class.values()) + idle
    say(f"identity: step programs {by_class['step'] * per_step:.3f} + "
        f"prefills {by_class['prefill'] * per_step:.3f} + other programs "
        f"{by_class['other'] * per_step:.3f} + idle {idle * per_step:.3f} "
        f"= {total * per_step:.3f} ms a step against a captured span of "
        f"{span * per_step:.3f} (residue {100 * (total - span) / span:+.2f}"
        f"%; {inside * per_step:.3f} of the idle time lies between two "
        "operations of one run and is in both)")
    return out


def analyse(run):
    """:func:`account` of the run's capture; once a run, whichever
    reading is asked for first."""
    if "_turn_account" not in run:
        run["_turn_account"] = None
        path = (run.get("trace") or {}).get("xplane")
        if path and os.path.exists(path):
            run["_turn_account"] = account(read_events(path))
    return run["_turn_account"]


def read(run, what):
    parts = analyse(run)
    return None if parts is None else parts[what]
