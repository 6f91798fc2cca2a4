"""The client's own reading of the window (``benchmark/run.py``,
``serve_results``): time from when a request fell due to its first token,
median over the requests that fell due inside the window. Too
unsteady between runs to carry a bound (PERF.md, section 2)."""


def read(run):
    return run["raw"].get("client", {}).get("ttft_p50_ms")
