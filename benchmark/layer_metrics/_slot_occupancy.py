"""Tokens generated for each decode step, as a share of the slots."""

from _lib import counters


def read(run):
    c = counters(run)
    if c is None or c[1]["steps"] == c[0]["steps"]:
        return None
    a, b, _ = c
    slots = run["cellfile"]["deployment"]["num_slots"]
    return (100.0 * (b["tokens_generated"] - a["tokens_generated"])
            / ((b["steps"] - a["steps"]) * slots))
