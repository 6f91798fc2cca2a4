"""Client send -> entry of the replica's ``stream``: proxy, routing and
the hop to the replica, on one host's monotonic clock."""

import statistics


def read(run):
    raw = run["raw"]
    if "open" not in raw:
        return None
    seen = raw["close"]["ingress_s"][len(raw["open"]["ingress_s"]):]
    return 1e3 * statistics.median(seen) if seen else None
