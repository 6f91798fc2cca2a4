"""Distributions the program keeps of its own walls, as differences
across the window: ``stats()["phase_walls"]`` (``{"edges_s": [...],
"counts": {phase: [...]}}``: each engine phase's wall counted into
geometric buckets, sixteen a doubling from 16 us to 4 s, one under and
one over) and ``stats()["delivery"]`` (over the same edges, how long the
oldest token a ``poll()`` handed over had lain in its request's output:
a token's way from the engine to its caller).

``read(run, of, pct=None, under_ms=None)``: of the phase ``of`` (or
``"delivery"``), the ``pct``-th percentile of its wall in ms (linear
inside a bucket: within the bucket's 4.4% of the exact one), or with
``under_ms`` the share of its entries shorter than the edge nearest to
it, in percent. None where the program keeps no such distribution or
nothing entered it in the window. A line ``[phase_walls] ...`` says what
was read, with the median beside it."""

from _lib import counters


def percentile(edges, counts, pct):
    """Linear inside the bucket; the two open buckets read as their one
    edge. ``counts[i]`` covers ``[edges[i - 1], edges[i])``."""
    total = sum(counts)
    if total <= 0:
        return None
    want, below = total * pct / 100.0, 0.0
    for i, n in enumerate(counts):
        if n and below + n >= want:
            if i == 0:
                return edges[0]
            if i == len(edges):
                return edges[-1]
            return edges[i - 1] + (edges[i] - edges[i - 1]) \
                * (want - below) / n
        below += n
    return edges[-1]


def window_counts(run, of):
    """(edges, the window's counts of ``of``, the two ``stats()``) or
    None."""
    c = counters(run)
    if c is None or "phase_walls" not in c[0] or "phase_walls" not in c[1]:
        return None
    edges = c[1]["phase_walls"]["edges_s"]
    if of == "delivery":
        if "delivery" not in c[0] or "delivery" not in c[1]:
            return None
        a = c[0]["delivery"]["pickup_wall_counts"]
        b = c[1]["delivery"]["pickup_wall_counts"]
    else:
        b = c[1]["phase_walls"]["counts"].get(of)
        if b is None:
            return None
        a = c[0]["phase_walls"]["counts"].get(of, [0] * len(b))
    return edges, [y - x for x, y in zip(a, b)], c


def read(run, of, pct=None, under_ms=None):
    got = window_counts(run, of)
    if got is None or sum(got[1]) <= 0:
        return None
    edges, counts, c = got
    n, p50 = sum(counts), 1e3 * percentile(edges, counts, 50)
    note = ""
    if of == "delivery":
        d = {k: c[1]["delivery"][k] - c[0]["delivery"][k]
             for k in ("polls", "polls_empty", "tokens_picked")}
        note = (f"; {d['polls']} polls, {d['polls_empty']} found nothing "
                f"({d['polls_empty'] / max(d['tokens_picked'], 1):.2f} a "
                f"token picked), {d['tokens_picked']} tokens picked")
    if under_ms is not None:
        i = min(range(len(edges)),
                key=lambda j: abs(edges[j] - under_ms / 1e3))
        value = 100.0 * sum(counts[:i + 1]) / n
        print(f"[phase_walls] {of}: {value:.2f}% of {n} entries under "
              f"{1e3 * edges[i]:.3f} ms, median {p50:.3f} ms{note}",
              flush=True)
        return value
    value = 1e3 * percentile(edges, counts, pct)
    print(f"[phase_walls] {of}: p{pct} {value:.3f} ms, median {p50:.3f} ms "
          f"over {n} entries{note}", flush=True)
    return value
