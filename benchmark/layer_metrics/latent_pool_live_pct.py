"""Blocks of the latent pool in use, mean of the window's two ends: how
full the cache that a deployment would hold is while the cell runs. None
where the engine has no pool called ``latent``."""

from _lib import counters


def read(run):
    c = counters(run)
    if c is None:
        return None
    pools = [st.get("kv_pools", {}).get("latent") for st in c[:2]]
    if not all(pools):
        return None
    return 100.0 * sum(1.0 - p["blocks_free"] / p["blocks_total"]
                       for p in pools) / 2
