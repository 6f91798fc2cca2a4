"""Compile requests of the worker between the window's ends (0 unless a
shape was not warmed up)."""


def read(run):
    raw = run["raw"]
    if "open" in raw:
        return (raw["close"]["compile_requests"]["requests"]
                - raw["open"]["compile_requests"]["requests"])
    return (raw["compile_requests_after"]["requests"]
            - raw["compile_requests_before"]["requests"])
