"""One chip's time in collectives on the ``XLA Ops`` line, over its busy
time. That line holds what the core itself runs in order: a collective
the compiler made asynchronous shows there as a ``-start`` and a
``-done``, and the ``-done``'s time is the wait that no other operation
hid; a synchronous one shows whole. So this is the share of the step
that communication costs, not the time the links are busy (which the
``Async XLA Ops`` line holds, and ``trace_reduce`` does not read).
None where the trace has no collective: on one chip, or where a trace
puts them out of the reduced trace's reach."""

import re

COLLECTIVE = re.compile(r"^(all-gather|reduce-scatter|all-reduce|all-to-all"
                        r"|collective-permute)(-start|-done)?$")


def read(run):
    trace = run["trace"]
    hit = [v[1] for v in trace["ops"].values() if COLLECTIVE.match(v[2])]
    if not hit or not trace["busy_s"]:
        return None
    return 100.0 * sum(hit) / trace["busy_s"]
