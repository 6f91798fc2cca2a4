"""The block turn's own counters (``serve/llm.py``, an engine whose
model generates by blocks), as differences of ``stats()`` across the
window: ``block_steps`` (steps of the turn), ``slot_steps`` (slots that
ran in them), ``commit_steps`` (the slot-steps among those that only
committed a decided block). A program without them gives None.

``tokens_per_block_step``: tokens generated for each block step AND
slot: ``block_length / (denoising_steps + 1)`` times the share of the
slots that ran, less what the last blocks' surplus and the first
blocks' prompt tails take. ``commit_step_share_pct``: of the slot-steps
that ran, the share that decided nothing (``1 / (denoising_steps + 1)``
where every block starts undecided)."""

from _lib import counters


def read(run, what):
    c = counters(run)
    if c is None or "block_steps" not in c[0] or "block_steps" not in c[1]:
        return None
    a, b, _ = c

    def delta(name):
        return b[name] - a[name]

    if what == "tokens_per_block_step":
        steps = delta("block_steps")
        slots = run["cellfile"]["deployment"]["num_slots"]
        return delta("tokens_generated") / (steps * slots) if steps else None
    if what == "commit_step_share_pct":
        ran = delta("slot_steps")
        return 100.0 * delta("commit_steps") / ran if ran else None
    raise ValueError(f"_block_counters: no reading called {what!r}")
