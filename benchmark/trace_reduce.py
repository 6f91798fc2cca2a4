"""From a profiler trace (``.xplane.pb``) to numbers.

Two steps, so that the arithmetic can be checked without a chip:
``read_xplane`` turns the file into plain lists (it needs jax's
``ProfileData`` and so runs in the worker that took the trace), and
``reduce_planes`` turns those lists into busy time, per-operation and
per-program sums and the longest idle gaps. ``benchmark/tests`` checks
``reduce_planes`` on a recorded excerpt and on hand-made events.

How a v5e trace is laid out (looked at by hand in PR 23, PERF.md section
3): one plane for each chip, named ``/device:TPU:<n>``, with the lines
``Steps``, ``XLA Modules``, ``XLA Ops``, ``Async XLA Ops`` and ``TC
Overlay``. ``XLA Modules`` has one event for each run of a compiled
program, named ``jit_<function>(<id>)``: the decode step is ``jit_step``,
every prefill bucket ``jit_prefill``, the train step ``jit_step`` too.
``XLA Ops`` has one event for each operation, and its name is the
operation's whole HLO line; a ``while`` loop (the scan over layers) has
an event of its own that spans its body's. A Pallas kernel is a
``custom-call`` named after the computation it sits in
(``closed_call.9``, ``checkpoint.24``), not after the kernel. Times are
nanoseconds.
"""

from __future__ import annotations

import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def profiler_options():
    """The device's events and the host's runtime events, without the
    Python tracer: it records every Python call of every thread, which
    slows the engine loop it is meant to watch."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str, rehearse: bool = False) -> dict:
    """{plane name: {line name: [[event name, start_ns, duration_ns]]}}
    for the device planes. ``rehearse``: on a CPU there is no device
    plane; every event of the host plane then stands in as an operation,
    so that the code after this runs (its numbers mean nothing)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if rehearse and plane.name == "/host:CPU":
            out[plane.name] = {OPS_LINE: [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for line in plane.lines for ev in line.events]}
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines[line.name] = [[ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)]
                                for ev in line.events]
        out[plane.name] = lines
    return out


def list_planes(path: str) -> list:
    """Every plane and line with its event count: for looking at a trace
    by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [[plane.name, [[line.name, len(list(line.events))]
                          for line in plane.lines]]
            for plane in data.planes]


def union_seconds(intervals) -> float:
    """Length of the union of [start, start + duration) intervals (ns)."""
    total, end = 0.0, None
    for start, dur in sorted(intervals):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total / 1e9


def idle_gaps(intervals, lo, hi, top: int = 10) -> list:
    """The longest stretches inside [lo, hi] (ns) covered by no
    interval, in seconds, longest first."""
    gaps, end = [], lo
    for start, dur in sorted(intervals):
        if start > end:
            gaps.append((start - end) / 1e9)
        end = max(end, start + dur)
    if hi > end:
        gaps.append((hi - end) / 1e9)
    return sorted(gaps, reverse=True)[:top]


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
CONTAINERS = ("while", "conditional", "call")


def parse_op(text: str):
    """An operation's event carries its whole HLO line:
    ``%fusion.12 = bf16[32,4096]{...} fusion(...), kind=kLoop``.
    -> (name ``fusion.12``, opcode ``fusion``, result type). A custom
    call's opcode carries its target: ``custom-call:tpu_custom_call`` is a
    Pallas kernel, ``custom-call:AllocateBuffer`` is not."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%"), "", ""
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else ""
    result = rest[:m.start()].strip() if m else ""
    if opcode == "custom-call":
        t = _TARGET.search(rest)
        opcode += ":" + (t.group(1) if t else "")
    return name.strip().lstrip("%"), opcode, result[:160]


def program_name(event_name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``."""
    return event_name.split("(")[0]


def reduce_planes(planes: dict) -> dict:
    """What the per-layer readers take from a trace.

    ``busy_s`` is the union of the operation intervals on a chip,
    averaged over the chips; ``ops`` maps an operation's name to [calls,
    seconds, opcode, result type], loops and calls left out because
    their events span the operations inside them; ``window_s`` is the traced window: the span from the first
    operation's start to the last one's end (the profiler's own start
    and stop take time in which operations run unrecorded). ``ops`` and
    ``programs`` sum durations by name over all chips and divide by the
    number of chips, so a four-chip trace reads as one chip's share.
    """
    chips = sorted(planes)
    if not chips:
        return {"chips": 0, "busy_s": 0.0, "window_s": 0.0,
                "ops": {}, "programs": {}, "idle_gaps_s": []}
    n = len(chips)
    busy, span_lo, span_hi = 0.0, None, None
    ops, programs, gaps = {}, {}, []
    for chip in chips:
        op_events = planes[chip].get(OPS_LINE, [])
        iv = [(s, d) for _, s, d in op_events]
        busy += union_seconds(iv)
        for text, s, d in op_events:
            name, opcode, result = parse_op(text)
            if opcode in CONTAINERS:
                continue    # a loop's event spans its body's operations
            row = ops.setdefault(name, [0, 0.0, opcode, result])
            row[0] += 1
            row[1] += d / 1e9
        for name, s, d in planes[chip].get(MODULES_LINE, []):
            programs.setdefault(program_name(name), []).append(d / 1e9)
        if iv:
            lo = min(s for s, _ in iv)
            hi = max(s + d for s, d in iv)
            span_lo = lo if span_lo is None else min(span_lo, lo)
            span_hi = hi if span_hi is None else max(span_hi, hi)
            if chip == chips[0]:
                gaps = idle_gaps(iv, lo, hi)
    span = 0.0 if span_lo is None else (span_hi - span_lo) / 1e9
    return {
        "chips": n,
        "busy_s": busy / n,
        "window_s": span,
        "ops": {k: [c / n, t / n, oc, res]
                for k, (c, t, oc, res) in ops.items()},
        "programs": {k: {"count": len(v) / n, "total_s": sum(v) / n,
                         "median_s": statistics.median(v)}
                     for k, v in programs.items()},
        "idle_gaps_s": gaps,
    }


def ops_seconds(summary: dict, name: str = ".", opcode: str | None = None,
                result: str | None = None):
    """(seconds, calls) of the operations whose name matches ``name``
    and, where given, whose opcode is ``opcode`` and whose result type
    matches ``result``. One chip's share."""
    rx, rr = re.compile(name), re.compile(result or ".")
    hit = [v for k, v in summary["ops"].items()
           if rx.search(k) and (opcode is None or v[2] == opcode)
           and (result is None or rr.search(v[3]))]
    return sum(v[1] for v in hit), sum(v[0] for v in hit)


def breakdown(summary: dict, top: int = 10) -> dict:
    ops = sorted(summary["ops"].items(), key=lambda kv: -kv[1][1])[:top]
    return {"device_ops": [[f"{v[2]}:{k}", v[1]] for k, v in ops],
            "idle_gaps": [["host", g] for g in summary["idle_gaps_s"][:top]]}


def write_excerpt(trace_dir: str, out_path: str, program: str = "jit_step",
                  text_limit: int = 300, only: str | None = None) -> None:
    """Cut one run of ``program`` out of a recorded trace and keep it,
    with what ``reduce_planes`` reads from it, as the test's fixture
    (``only``: just the operations whose opcode matches, where a whole
    run is too long to keep). Also writes the full text of every distinct
    custom call beside it (how the kernels are named is read from there
    by hand)."""
    import json

    path = find_xplane(trace_dir)
    planes = read_xplane(path)
    chip = sorted(planes)[0]
    mods = planes[chip][MODULES_LINE]
    runs = [m for m in mods if program_name(m[0]) == program]
    name, lo, dur = runs[len(runs) // 2]
    def short(text):        # the head, the opcode, a custom call's target
        if len(text) <= text_limit:
            return text
        _, opcode, _ = parse_op(text)
        t = _TARGET.search(text)
        head = text[:text_limit].split(", custom_call_target=")[0]
        return (head + f" {opcode.split(':')[0]}(...)"
                + (", " + t.group(0) if t else ""))

    keep = lambda evs: [[short(t), s, d] for t, s, d in evs  # noqa: E731
                        if s >= lo and s + d <= lo + dur]
    ops = keep(planes[chip][OPS_LINE])
    if only:
        ops = [ev for ev in ops if re.search(only, parse_op(ev[0])[1])]
    cut = {chip: {OPS_LINE: ops, MODULES_LINE: keep(mods)}}
    summary = reduce_planes(cut)
    top = sorted(summary["ops"].items(), key=lambda kv: -kv[1][1])[:8]
    with open(out_path, "w") as f:
        json.dump({"from": os.path.basename(path), "program": program,
                   "planes": cut,
                   "expect": {"busy_s": summary["busy_s"],
                              "ops": {k: v[:3] for k, v in top},
                              "programs": [program]}}, f)
    seen = {}
    for text, _, _ in planes[chip][OPS_LINE]:
        n, opcode, _ = parse_op(text)
        if opcode.startswith("custom-call"):
            seen.setdefault(n, text)
    with open(out_path + ".custom_calls.txt", "w") as f:
        for n, text in seen.items():
            f.write(f"{n}\n{text[:6000]}\n\n")


if __name__ == "__main__":
    import sys

    write_excerpt(*sys.argv[1:3], **dict(a.split("=", 1)
                                         for a in sys.argv[3:]))
