"""Window over decode steps: the engine loop's whole turn (admit,
prefill, decode dispatch, logits fetch, sampling), not the device's."""

from _lib import counters


def read(run):
    c = counters(run)
    if c is None or c[1]["steps"] == c[0]["steps"]:
        return None
    return 1e3 * c[2] / (c[1]["steps"] - c[0]["steps"])
