"""Peak bytes in use on the worker's fullest chip, in 1e9 bytes."""


def read(run):
    peak = run["raw"]["device"].get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
