"""Device time of a program's runs by the named part of the block that
each operation belongs to.

The program opens ``ray_tpu.util.profiling.part(name)`` (one vocabulary,
the tuples there whose names end in ``PARTS``) around the block's
arithmetic. ``PARTS`` here is a copy, because a reader imports nothing of
the program, and it is DATA: the union of the lists in
``layer_metrics/parts/*.json`` (``base.json`` the block's own,
``state_space.json`` what a state-space model adds), so a PR that adds a
model adds ``parts/<architecture>.json`` beside the names it appends in
``profiling.py`` and edits nothing here;
``benchmark/tests/test_dev_ms_by_part.py`` holds the two unions equal. The
name is a component of every instruction's ``op_name``:
``jit(step)/while/body/closed_call/mlp/dot_general``. In a v5e capture
that path is the ``tf_op`` stat (the path and a trailing ``:``) of the
operation's EVENT METADATA on the ``XLA Ops`` line. jaxlib's
``ProfileData`` shows an event's own stats only (``device_offset_ps``,
``device_duration_ps``), so this file parses the ``.xplane.pb`` itself: a
few fields of five messages of ``xplane.proto``, nothing else of it.

Of the first chip's plane, each ``XLA Ops`` event that starts inside a
run (an ``XLA Modules`` event) of a program whose name matches is filed:
a container (``while``, ``conditional``, ``call``) is skipped, its event
spans its body's; a collective's opcode makes it ``collective`` whatever
its path; else its part is the innermost component of the path that is a
name of ``PARTS``, a transform's wrapper peeled off
(``transpose(jvp(mlp))``); else the part of the nearest operation that
CONSUMES its result (:func:`file_program`: a layer scan's own
``dynamic_slice`` of the stacked weights names no part, the compiler's
own prefetches and copies carry no path at all; the table prints what
each part got that way); else ``unnamed``. A train step's operation is
also ``recompute`` (a path through ``rematted_computation``), ``backward``
(through ``transpose(jvp`` and not that) or ``forward``. A fusion carries
the path of ONE of the operations fused into it, so a part's number is
"operations whose fusion is filed under it". The parts sum to the run's
busy time (the union of its operations; an instant that two operations
cover counts for the earlier one).

``read(run, program, parts=None, phase=None, share=False)``:
milliseconds of one run of the programs matching ``program`` (the mean
over the capture's runs, whatever their program id) spent in ``parts``
and ``phase``; with ``share`` the percentage of the runs' busy time.
None where the capture has no run of the program, or none of its
operations lies under ``parts`` (a program traced before the names
were there). The whole table of every program it saw is printed once a
run, ``[dev_ms_by_part] ...``, with the longest unnamed operations."""

import bisect
import json
import os
import re
import time

from collective_exposed_pct import COLLECTIVE as COLLECTIVE_OP

from benchmark.trace_reduce import (CONTAINERS, DEVICE_PLANE, MODULES_LINE,
                                    OPS_LINE, find_xplane, parse_op)

PARTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "parts")


def load_parts(folder=PARTS_DIR):
    """The vocabulary: every name of every ``<folder>/*.json``'s
    ``"parts"`` list, files in name order, a name once."""
    names = []
    for entry in sorted(os.listdir(folder)):
        if entry.endswith(".json"):
            with open(os.path.join(folder, entry)) as f:
                names += [n for n in json.load(f)["parts"] if n not in names]
    return tuple(names)


PARTS = load_parts()
UNNAMED, COLLECTIVE = "unnamed", "collective"
PATH_STAT = "tf_op"     # the event metadata's stat that holds ``op_name``
PHASES = ("forward", "recompute", "backward")
DEPTH = 4       # path-less operations between one and its named consumer
_PEEL = re.compile(r"^(?:\w+\()*([^()]*)\)*$")
_KNOWN = frozenset(PARTS)


# ------------------------------------------------------------ the file
def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane_name(buf):
    for f, v in _fields(buf):
        if f == 2:
            return _text(v)
    return ""


def _event_metadata(buf, path_stat):
    """XEventMetadata -> (name, the string of its stat ``path_stat``)."""
    name, path = "", ""
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 5:                    # XStat: metadata_id 1, str_value 5
            stat = dict(_fields(v))
            if stat.get(1) == path_stat and 5 in stat:
                path = _text(stat[5])
    return name, path


def _event(buf):
    """(offset_ps, duration_ps, metadata id) of an XEvent: its first
    three fields, in the order a serializer writes them; its stats,
    which follow, are not looked at (a capture holds some 10^5 events)."""
    meta = offset = dur = 0
    i, n = 0, len(buf)
    while i < n:
        key = buf[i]
        if key == 0x08:
            meta, i = _varint(buf, i + 1)
        elif key == 0x10:
            offset, i = _varint(buf, i + 1)
        elif key == 0x18:
            dur, i = _varint(buf, i + 1)
        else:
            break
    return offset, dur, meta


def _events(line_buf):
    """(line name, [(start_ps, duration_ps, metadata id)]) of an XLine."""
    name, t0, events = "", 0, []
    for f, v in _fields(line_buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0 = v                                  # timestamp_ns
        elif f == 4 and name in (MODULES_LINE, OPS_LINE):
            events.append(_event(v))
    return name, [(t0 * 1000 + o, d, m) for o, d, m in events]


def read_capture(path):
    """(runs, operations) of the first chip's plane of an ``.xplane.pb``:
    runs [(start_ps, duration_ps, program name with its id)] from the
    ``XLA Modules`` line, operations [(start_ps, duration_ps, HLO line,
    ``tf_op`` path)] from ``XLA Ops``. ([], []) without a device plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for f, v in _fields(space):
        if f == 1:
            m = DEVICE_PLANE.match(_plane_name(v))
            if m:
                planes[int(m.group(1))] = v
    if not planes:
        return [], []
    lines, meta_bufs, path_stat = [], {}, None
    for f, v in _fields(planes[min(planes)]):
        if f == 3:
            lines.append(v)
        elif f == 4:
            key, value = _map_entry(v)
            meta_bufs[key] = value
        elif f == 5:
            key, value = _map_entry(v)
            if _text(dict(_fields(value)).get(2, b"")) == PATH_STAT:
                path_stat = key
    meta = {k: _event_metadata(v, path_stat) for k, v in meta_bufs.items()}
    runs, ops = [], []
    for buf in lines:
        name, events = _events(buf)
        if name == MODULES_LINE:
            runs = [(s, d, meta[m][0]) for s, d, m in events]
        elif name == OPS_LINE:
            ops = [(s, d, *meta[m]) for s, d, m in events]
    return runs, ops


# ------------------------------------------------------- the arithmetic
def part_of(path):
    """The innermost component of an ``op_name`` path that is a part."""
    for comp in reversed(path.rstrip(":").split("/")):
        inner = _PEEL.match(comp)
        if inner and inner.group(1) in _KNOWN:
            return inner.group(1)
    return UNNAMED


def phase_of(path):
    if "rematted_computation" in path:
        return "recompute"
    return "backward" if "transpose(jvp" in path else "forward"


_CALLED = re.compile(r"\w+=(%[\w.\-]+|\{[^}]*\})")
_OPERAND = re.compile(r"%([\w.\-]+)")


def operands(text):
    """The instructions an operation's HLO line takes as operands (the
    computations it calls are not among them)."""
    rhs = text.partition(" = ")[2]
    return _OPERAND.findall(_CALLED.sub("", rhs))


def file_program(instructions):
    """{instruction name: (part, path, by_consumer)} of one program's
    ``{name: (opcode, HLO line, path)}``. An operation whose own path
    names no part, the compiler's own among them (a weight's prefetch in
    ``slice-start`` / ``slice-done`` / ``ConcatBitcast``, a ``copy``), is
    filed under the part of the nearest operation that consumes its
    result (``by_consumer`` True), through at most ``DEPTH`` path-less
    ones between; a loop's event is no consumer. Else ``unnamed``."""
    own, users = {}, {}
    for name, (opcode, text, path) in instructions.items():
        own[name] = (COLLECTIVE if COLLECTIVE_OP.match(opcode)
                     else part_of(path))
        for operand in operands(text):
            if operand in instructions and opcode not in CONTAINERS:
                users.setdefault(operand, []).append(name)
    out = {}
    for name, (_, _, path) in instructions.items():
        part, found, frontier = own[name], None, [name]
        for _ in range(DEPTH if part == UNNAMED else 0):
            frontier = [u for n in frontier for u in users.get(n, ())]
            found = next((u for u in frontier if own[u] != UNNAMED), None)
            if found or not frontier:
                break
        out[name] = ((own[found], instructions[found][2], True) if found
                     else (part, path, False))
    return out


def by_part(runs, ops, program="."):
    """{program name with its id: {"runs", "wall_ps", "busy_ps", "parts":
    {(part, phase): ps}, "by_consumer": {part: ps of it filed by a
    consumer's path}, "lent": {instruction name: [ps, its consumer's
    part, its own path]}, "unnamed": {instruction name: [ps, path]}}} over
    the runs whose name matches ``program``."""
    rx = re.compile(program)
    hit = sorted((s, d, n) for s, d, n in runs if rx.search(n))
    starts = [s for s, _, _ in hit]
    out, cursor, events, programs, parsed = {}, {}, [], {}, {}
    for s, d, n in hit:
        row = out.setdefault(n, {"runs": 0, "wall_ps": 0, "busy_ps": 0,
                                 "parts": {}, "by_consumer": {},
                                 "lent": {}, "unnamed": {}})
        row["runs"] += 1
        row["wall_ps"] += d
    for s, d, text, path in sorted(ops, key=lambda op: op[:2]):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= hit[i][0] + hit[i][1]:
            continue                    # outside every run of the program
        if text not in parsed:          # one HLO line, some 10^2 events
            parsed[text] = parse_op(text)[:2]
        name, opcode = parsed[text]
        programs.setdefault(hit[i][2], {})[name] = (opcode, text, path)
        if opcode in CONTAINERS:
            continue
        # what no earlier operation of this run covers
        took = max(0, s + d - max(s, cursor.get(i, 0)))
        cursor[i] = max(cursor.get(i, 0), s + d)
        events.append((hit[i][2], name, took))
    filed = {n: file_program(instrs) for n, instrs in programs.items()}
    for prog, name, took in events:
        part, path, by_consumer = filed[prog][name]
        row = out[prog]
        row["busy_ps"] += took
        key = (part, phase_of(path))
        row["parts"][key] = row["parts"].get(key, 0) + took
        if by_consumer:
            row["by_consumer"][part] = row["by_consumer"].get(part, 0) + took
            own = programs[prog][name][2]
            row["lent"].setdefault(name, [0, part, own])[0] += took
        if part == UNNAMED:
            seen = row["unnamed"].setdefault(name, [0, path])
            seen[0] += took
    for row in out.values():
        assert sum(row["parts"].values()) == row["busy_ps"], row
    return out


def table(row):
    """One program's line of the printed table: ms a run and share."""
    runs, busy = row["runs"], row["busy_ps"] or 1
    parts = {}
    for (part, _), ps in row["parts"].items():
        parts[part] = parts.get(part, 0) + ps
    lent = row["by_consumer"]
    cells = [f"{p} {ps / runs / 1e9:.3f} ms {100 * ps / busy:.1f}%"
             + (f" ({lent[p] / runs / 1e9:.3f} by consumer)"
                if lent.get(p) else "")
             for p, ps in sorted(parts.items(), key=lambda kv: -kv[1])]
    phases = {}
    for (_, phase), ps in row["parts"].items():
        phases[phase] = phases.get(phase, 0) + ps
    if set(phases) - {"forward"}:
        cells += [f"{ph} {100 * phases.get(ph, 0) / busy:.1f}%"
                  for ph in PHASES]
    return (f"{runs} runs of {row['wall_ps'] / runs / 1e9:.3f} ms, busy "
            f"{row['busy_ps'] / runs / 1e9:.3f} ms: " + " | ".join(cells))


# ------------------------------------------------------------- the run
def capture_path(run):
    """The run's ``.xplane.pb``: where the worker says it is, else the
    newest under the cell's output directory (the train worker reduces
    its capture and does not say where it lies)."""
    path = (run.get("trace") or {}).get("xplane")
    if path and os.path.exists(path):
        return path
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        return find_xplane(os.path.join(here, ".out", run["cell"]["name"],
                                        "trace"))
    except FileNotFoundError:
        return None


def analyse(run):
    """:func:`by_part` of every program of the run's capture, read once
    for all the metrics of a run; the whole table is printed then."""
    if "_dev_ms_by_part" not in run:
        run["_dev_ms_by_part"] = {}
        path = capture_path(run)
        if path is not None:
            t0 = time.monotonic()
            try:
                runs, ops = read_capture(path)
            except (IndexError, KeyError, ValueError) as err:
                # a file cut short or of another layout: no reading,
                # said once; the run's line goes out without the metrics
                print(f"[dev_ms_by_part] {path} not read: {err!r}",
                      flush=True)
                return run["_dev_ms_by_part"]
            rows = run["_dev_ms_by_part"] = by_part(runs, ops)
            for name, row in sorted(rows.items()):
                print(f"[dev_ms_by_part] {name}: {table(row)}", flush=True)
                for kind, ops_of in (("unnamed", row["unnamed"]),
                                     ("by consumer", row["lent"])):
                    worst = sorted(ops_of.items(),
                                   key=lambda kv: -kv[1][0])[:6]
                    for op, (ps, *where) in worst:
                        print(f"[dev_ms_by_part]   {kind} {op} "
                              f"{ps / row['runs'] / 1e9:.4f} ms a run, "
                              + " <- path ".join(w or "-" for w in where),
                              flush=True)
            print(f"[dev_ms_by_part] read {len(ops)} operations of "
                  f"{os.path.getsize(path) / 1e6:.1f} MB in "
                  f"{time.monotonic() - t0:.2f} s", flush=True)
    return run["_dev_ms_by_part"]


def read(run, program, parts=None, phase=None, share=False):
    rx = re.compile(program)
    rows = [row for name, row in analyse(run).items() if rx.search(name)]
    n = sum(row["runs"] for row in rows)
    busy = sum(row["busy_ps"] for row in rows)
    took = [ps for row in rows for (part, ph), ps in row["parts"].items()
            if (parts is None or part in parts)
            and (phase is None or ph == phase)]
    if not n or not busy or not took:
        return None
    return 100.0 * sum(took) / busy if share else sum(took) / n / 1e9
