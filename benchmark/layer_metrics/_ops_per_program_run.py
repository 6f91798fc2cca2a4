"""Device time of the operations whose instruction name matches, for
each run of a program: the seconds of ``ops`` (and, where given, of that
``opcode``) in the traced window over the runs of ``program`` the trace
shows. For operations that only that program calls (a kernel whose
``name=`` is its own). A trace without the operations, or without a run
of the program, gives None."""

from _lib import ops_seconds, programs


def read(run, ops, program, opcode=None):
    runs = sum(p["count"] for p in programs(run["trace"], program))
    seconds, calls = ops_seconds(run["trace"], ops, opcode=opcode)
    if not runs or not calls:
        return None
    return 1e3 * seconds / runs
