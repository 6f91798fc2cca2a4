"""``fit()`` -> the train loop starts in the worker: placement group,
worker boot, the chip's grant, jax's start."""


def read(run):
    raw = run["raw"]
    if "loop_start" not in raw:
        return None
    return raw["loop_start"] - raw["t_fit"]
