"""The expert layer's own counters and the window pool's fill, as
differences of ``stats()`` across the window. The engine sums what each
decode step's program reports (``model_counters``: routed-layer calls,
(token, expert) pairs computed on this chip, experts that had a token,
the largest expert's load over the mean, pairs dropped) and keeps the
prefills' apart (``model_counters_prefill``). A program without them
gives None.

``pairs_per_step`` and ``load_max_over_mean``: means over the routed
layers' calls of the decode steps. ``pairs_dropped``: decode steps and
prefills together; the layer has no capacity, so anything but 0 is a
fault. ``window_pool_live_pct``: blocks of the window layers' pool in
use, mean of the window's two ends."""

from _lib import counters

DECODE, PREFILL = "model_counters", "model_counters_prefill"


def read(run, what):
    c = counters(run)
    if c is None or DECODE not in c[0] or DECODE not in c[1]:
        return None
    a, b = c[0], c[1]

    def delta(group, name):
        return b[group][name] - a[group][name]

    if what == "pairs_dropped":
        return (delta(DECODE, "expert_pairs_dropped")
                + delta(PREFILL, "expert_pairs_dropped"))
    if what == "window_pool_live_pct":
        fill = [1.0 - st["kv_pools"]["window"]["blocks_free"]
                / st["kv_pools"]["window"]["blocks_total"] for st in (a, b)]
        return 100.0 * sum(fill) / 2
    calls = delta(DECODE, "expert_layer_calls")
    if not calls:
        return None
    if what == "pairs_per_step":
        return delta(DECODE, "expert_pairs") / calls
    if what == "load_max_over_mean":
        return delta(DECODE, "expert_load_max_over_mean") / calls
    raise ValueError(f"_moe_counters: no reading called {what!r}")
