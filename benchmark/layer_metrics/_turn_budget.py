"""What an engine turn's wall is made of, from the loop's own phases
(``stats()["phases"]``, differences across the WHOLE window, not the
capture): the engine thread waiting for the decode step, waiting for an
admission's prefill, and doing its own work.

Every blocking read of the device by the engine thread sits in a phase
whose name ends in ``_fetch`` (``ray_tpu/serve/llm.py``):
``logits_fetch`` / ``block_fetch`` / ``spec_fetch`` wait for the step,
``prefill_fetch`` for a prefill (inside ``prefill``, whose self wall is
then the dispatch alone). Over the decode steps of the window:

- ``decode_wait``: self wall of the three step fetches;
- ``prefill_wait``: ``prefill_fetch``'s (what an admission that did not
  block the turn would give back);
- ``host``: the wall of ``turn`` less the two: the thread's own work.

The three sum to ``turn``'s wall a step, which is ``engine_step_ms``
less what lies between two turns. A program whose phases have no
``prefill_fetch`` row cannot tell an admission's wait from its work:
``prefill_wait`` and ``host`` read None there, not 0. Printed once a
run, ``[turn_budget] ...``: the three, their sum, ``engine_step_ms`` and
every phase's self ms a step."""

from _lib import counters

STEP_FETCHES = ("logits_fetch", "block_fetch", "spec_fetch")
WALL, SELF = 1, 2


def budget(run):
    """{what: ms a decode step} (None where it cannot be told), once a
    run; None where the run has no phases or no step."""
    if "_turn_budget" in run:
        return run["_turn_budget"]
    run["_turn_budget"] = None
    c = counters(run)
    if c is None or "phases" not in c[0] or "phases" not in c[1]:
        return None
    a, b = c[0]["phases"], c[1]["phases"]
    steps = c[1]["steps"] - c[0]["steps"]
    if not steps or "turn" not in b:
        return None
    zero = [0, 0.0, 0.0, 0.0, 0.0]

    def ms(name, column=SELF):
        return 1e3 * (b.get(name, zero)[column]
                      - a.get(name, zero)[column]) / steps

    out = {"decode_wait": sum(ms(n) for n in STEP_FETCHES),
           "prefill_wait": None, "host": None}
    line = f"decode_wait {out['decode_wait']:.3f}"
    if "prefill_fetch" in b:
        out["prefill_wait"] = ms("prefill_fetch")
        out["host"] = (ms("turn", WALL) - out["decode_wait"]
                       - out["prefill_wait"])
        line += (f" + prefill_wait {out['prefill_wait']:.3f} + host "
                 f"{out['host']:.3f} = turn {ms('turn', WALL):.3f}")
    else:
        line += (f" of turn {ms('turn', WALL):.3f} (no prefill_fetch row: "
                 "an admission's wait is not told from its work)")
    selfs = sorted(((ms(n), n) for n in b), reverse=True)
    print(f"[turn_budget] ms a decode step over {steps} steps: {line}; "
          f"engine_step_ms {1e3 * c[2] / steps:.3f}; self: "
          + ", ".join(f"{n} {v:.3f}" for v, n in selfs if v >= 0.0005),
          flush=True)
    run["_turn_budget"] = out
    return out


def read(run, what):
    out = budget(run)
    return None if out is None else out[what]
