"""Shared arithmetic of the readers. A reader gets ``run``: the cell, its
configuration, mix and cell file, what the run collected (``raw``,
``res``), the reduced trace (``trace``) and the device's peaks; it
returns a number, or None where it finds nothing to read."""

import re

from benchmark.trace_reduce import ops_seconds  # noqa: F401 — for readers


def counters(run):
    """(stats at window open, stats at close, seconds between them) of a
    serve cell, None elsewhere."""
    raw = run["raw"]
    if "open" not in raw:
        return None
    return (raw["open"]["stats"], raw["close"]["stats"],
            raw["close"]["now"] - raw["open"]["now"])


def programs(trace, pattern):
    rx = re.compile(pattern)
    return [v for k, v in trace["programs"].items() if rx.search(k)]
