"""Median device time of one run of the programs whose name matches."""

import statistics

from _lib import programs


def read(run, program):
    hit = programs(run["trace"], program)
    if not hit:
        return None
    return 1e3 * statistics.median(p["median_s"] for p in hit)
