"""The one general traffic generator. A mix is a data file under
``benchmark/traffic/``; this turns it and ``--seed`` into a schedule.

Every seed gets THE SAME set of sizes and gaps, in another order: the
lengths of a block of requests are the quantile midpoints of the mix's
distributions (so the work of a block is fixed), and the seed only
permutes them and draws the token ids. An open loop's window is exactly
one block. Runs with different seeds then differ by order, never by
amount of work; a mix with ``order_seed`` pins the order too (on the
chip the order alone moved the chat cell's gaps by 10%, PERF.md). Pure Python and
numpy: the driver process stays off jax.
"""

from __future__ import annotations

import json
import math
import os
import statistics

import numpy as np

from benchmark import model_spec

_N = statistics.NormalDist()


def load_mix(name: str, root: str = model_spec.HERE) -> dict:
    path = os.path.join(root, "traffic", name + ".json")
    if not os.path.exists(path):
        raise SystemExit(f"no traffic mix {name!r} under benchmark/traffic/")
    with open(path) as f:
        return json.load(f)


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` whole lengths at the quantile midpoints of ``dist``."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([_N.inv_cdf(float(x)) for x in u])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        vals = dist["min"] + u * (dist["max"] - dist["min"])
    elif dist["dist"] == "fixed":
        vals = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"length distribution {dist['dist']!r}")
    lo = dist.get("min", 1)
    hi = dist.get("max", max(lo, int(vals.max())))
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def exponential_gaps(n: int, rate_per_s: float, cv: float = 1.0
                     ) -> np.ndarray:
    """``n`` gaps at the quantile midpoints of an exponential (cv 1) or,
    for bursts, a Weibull with that coefficient of variation, scaled so
    that they sum to exactly ``n / rate``."""
    u = (np.arange(n) + 0.5) / n
    if cv == 1.0:
        gaps = -np.log1p(-u)
    else:
        # Weibull shape k from the cv by bisection (cv falls as k grows)
        lo, hi = 0.05, 20.0
        for _ in range(60):
            k = (lo + hi) / 2
            c = math.sqrt(math.gamma(1 + 2 / k) / math.gamma(1 + 1 / k) ** 2
                          - 1)
            lo, hi = (k, hi) if c > cv else (lo, k)
        gaps = (-np.log1p(-u)) ** (1 / k)
    return gaps * (n / rate_per_s) / gaps.sum()


def seeded_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def _block(mix, n, duration_s, order_rng, token_rng, vocab):
    """``n`` requests: the quantile midpoints of the mix's lengths and,
    for an open loop, gaps that sum to exactly ``duration_s``; three
    independent permutations from the seed, so a long prompt need not
    meet a long answer or a short gap, but every block holds each once.
    Offsets are from the block's start; the first request falls due half
    its gap in, so that all ``n`` fall inside the block."""
    plens = quantile_lengths(mix["prompt_len"], n)
    olens = quantile_lengths(mix["output_len"], n)
    pp, po, pg = (order_rng.permutation(n) for _ in range(3))
    if duration_s:
        gaps = exponential_gaps(n, n / duration_s,
                                mix.get("arrival_cv", 1.0))[pg]
        offsets = np.cumsum(gaps) - gaps[0] / 2
    else:
        offsets = np.zeros(n)
    for j in range(n):
        yield float(offsets[j]), {
            "prompt": token_rng.integers(0, vocab, int(plens[pp[j]])).tolist(),
            "max_tokens": int(olens[po[j]])}


def request_stream(mix: dict, seed: int, vocab: int, seconds: float = 0.0):
    """Endless iterator of ``{"i", "gap_s", "prompt", "max_tokens"}``.
    ``gap_s`` is the time since the previous request fell due (0 for a
    closed loop, whose blocks hold ``block`` requests). An open loop
    sends one block over the lead-in (``lead_s``) and then one block for
    each window of ``seconds``: every seed puts the same
    ``round(rate * seconds)`` requests inside the window."""
    # a mix may pin the order (``order_seed``): then seeds differ by token
    # ids (and weights) alone, and runs of different seeds can be compared
    order_rng = seeded_rng(mix.get("order_seed", seed), 1)
    token_rng = seeded_rng(seed, 2)
    rate = mix.get("rate_per_s")
    i, start, last_due = 0, 0.0, 0.0
    durations = [mix["lead_s"]] if rate else []
    while True:
        duration = durations.pop(0) if durations else (seconds if rate else 0.0)
        n = max(1, round(rate * duration)) if rate else mix.get("block", 64)
        for off, req in _block(mix, n, duration, order_rng, token_rng, vocab):
            due = start + off
            yield dict(req, i=i, gap_s=due - last_due)
            last_due, i = due, i + 1
        start += duration


def prompt_buckets(mix: dict) -> list:
    """The engine's prefill buckets this mix's prompts fall into (the
    shapes a cell warms up, and no others)."""
    buckets = (64, 128, 256, 512, 1024, 2048)
    plens = quantile_lengths(mix["prompt_len"], mix.get("block", 64))
    out = set()
    for n in plens:
        out.add(next((b for b in buckets if n <= b), None))
    if None in out:
        raise SystemExit("a prompt of this mix is longer than the largest "
                         "prefill bucket (2048)")
    return sorted(out)


def train_batches(mix: dict, seed: int, vocab: int, batch: int):
    """Endless iterator of (batch, seq) int32 token arrays: a fresh
    batch for every step from a seeded host generator."""
    rng = seeded_rng(seed, 3)
    while True:
        yield rng.integers(0, vocab, (batch, mix["seq"]), dtype=np.int32)
