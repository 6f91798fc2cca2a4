"""Device time of the programs of a turn that are neither the step nor a
prefill (the programs that pick tokens, ``jit_greedy_ids`` /
``jit_sample_ids``, ``jit_merge_ids``, ``jit_seat_blocks``, a block
turn's ``jit_block_decide``, the uploads' conversions), over the decode
steps of the capture, in ms a step. The table of them by name is
printed, and with it the capture's whole account (``_turn_account``:
this program class is one term of its identity), so a cell that lists no
gap metric prints it too."""

import re

import _turn_account

STEP = re.compile(r"^jit_(step|block_step)")
PREFILL = re.compile(r"^jit_prefill")


def read(run):
    programs = run["trace"]["programs"]
    steps = sum(p["count"] for k, p in programs.items() if STEP.search(k))
    if not steps:
        return None
    _turn_account.analyse(run)
    other = {k: p for k, p in programs.items()
             if not STEP.search(k) and not PREFILL.search(k)}
    total = sum(p["total_s"] for p in other.values())
    print(f"[other_programs] {1e3 * total / steps:.4f} ms a step over "
          f"{steps:g} decode steps: " + ", ".join(
              f"{k} {1e3 * p['total_s'] / steps:.4f} ({p['count']:g} runs, "
              f"median {1e6 * p['median_s']:.1f} us)"
              for k, p in sorted(other.items(),
                                 key=lambda kv: -kv[1]["total_s"])),
          flush=True)
    return 1e3 * total / steps
