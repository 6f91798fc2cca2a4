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


def live_kv_tokens(run):
    """Cached tokens a decode step reads, as the mean of the window's
    two ends: the allocator's blocks in use, less half a block for each
    active slot. None outside a serve cell."""
    c = counters(run)
    if c is None:
        return None
    live = []
    for st in c[:2]:
        blocks = st["kv_blocks_total"] - st["kv_blocks_free"]
        live.append(max(0.0, blocks * st["kv_block_size"]
                        - st["active_slots"] * st["kv_block_size"] / 2))
    return sum(live) / 2
