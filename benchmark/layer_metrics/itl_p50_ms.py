"""The client's own reading of the window (``benchmark/run.py``,
``serve_results``): gap between consecutive token events of a stream,
median over the requests that fell due inside the window. The
95th percentile carries the bound; this stands beside it."""


def read(run):
    return run["raw"].get("client", {}).get("itl_p50_ms")
