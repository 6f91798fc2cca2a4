"""Decode steps the engine gave the device BEHIND a step whose ids the
host had not read yet, as a share of the decode steps it gave it in the
window: ``stats()["turns"]["overlapped"]`` over ``overlapped + drained``
(``serve/llm.py``: a drained step starts from the host's tokens with
the device empty: a request's first step into an empty engine, the step
after a preemption, a speculative engine's every step). The chip waits
for the host's fetch and bookkeeping between two steps only where the
second is a drained one, so this is the share of the steps whose host
work ran under the device. The ids that ran past a request's end and
were thrown away (``surplus_dropped``: only an EOS or a cancel costs
one) are printed beside it. None where the program has no such row."""

from _lib import counters


def read(run):
    c = counters(run)
    if c is None or "turns" not in c[0] or "turns" not in c[1]:
        return None
    gone = {k: c[1]["turns"][k] - c[0]["turns"][k] for k in c[1]["turns"]}
    steps = gone["overlapped"] + gone["drained"]
    if not steps:
        return None
    print(f"overlapped_turn_pct: {gone['overlapped']} of {steps} decode "
          f"steps, {gone['surplus_dropped']} ids dropped", flush=True)
    return 100.0 * gone["overlapped"] / steps
