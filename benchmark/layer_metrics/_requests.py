"""A percentile of the time between two marks of a request's life in the
engine, over the requests enqueued inside the window.

The engine (``ray_tpu/serve/llm.py``) keeps one record for each finished
request: ``[enqueued_at, admitted_at, first_token_at, first_picked_at,
finished_at, prompt_len, output_len, preemptions, status]``, times on
``time.monotonic()`` of the replica, the clock of ``bench_info()["now"]``.
``stats()["requests"]`` holds the last 512 of them and the count of all;
a program without the records (or a ring that lost records of the
window) gives None."""

from benchmark.loadgen import percentile

MARKS = {"enqueued": 0, "admitted": 1, "first_token": 2, "first_picked": 3,
         "finished": 4}


def window_records(requests, t_open, t_close):
    """The records enqueued inside [t_open, t_close], or None where the
    ring has dropped a record that may have been one of them: records
    leave in the order they finished, so nothing of the window is lost
    while the oldest one kept finished before the window opened."""
    if not requests:
        return None
    recent = requests["recent"]
    dropped = requests["finished"] - len(recent)
    if dropped > 0 and (not recent
                        or recent[0][MARKS["finished"]] > t_open):
        return None
    return [r for r in recent if t_open <= r[MARKS["enqueued"]] <= t_close]


def read(run, start, end, pct):
    raw = run["raw"]
    if "final" not in raw:
        return None
    recs = window_records(raw["final"]["stats"].get("requests"),
                          raw["open"]["now"], raw["close"]["now"])
    a, b = MARKS[start], MARKS[end]
    spans = [r[b] - r[a] for r in recs or ()
             if r[a] is not None and r[b] is not None]
    if not spans:
        return None
    print(f"[requests] {start} -> {end}: p{pct} over {len(spans)} requests "
          f"of the window, longest {1e3 * max(spans):.1f} ms", flush=True)
    return 1e3 * percentile(spans, pct)
