"""Blocks of the dense KV pool in use, mean of the window's two ends: how
full the one pool a deployment holds is while the cell runs (a model
with a pool a kind has a reader a kind: ``latent_pool_live_pct``,
``window_pool_live_pct``). None where the engine reports no such pool."""

from _lib import counters


def read(run):
    c = counters(run)
    if c is None:
        return None
    ends = [st for st in c[:2] if st.get("kv_blocks_total")
            and st.get("kv_blocks_free") is not None]
    if len(ends) < 2:
        return None
    return 100.0 * sum(1.0 - st["kv_blocks_free"] / st["kv_blocks_total"]
                       for st in ends) / 2
