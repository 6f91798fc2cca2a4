"""Device time of the prefill programs over the device's busy time."""

from _lib import programs


def read(run):
    trace = run["trace"]
    hit = programs(trace, r"^jit_prefill")
    if not hit or not trace["busy_s"]:
        return None
    return 100.0 * sum(p["total_s"] for p in hit) / trace["busy_s"]
