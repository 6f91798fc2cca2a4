"""The mean pass (0 .. T - 1) after which a decode step's rows would
leave a model whose layers run several times, under the exit gate's
distribution: ``sum_t t * exit_p<t> / exit_rows`` of the engine's
``model_counters`` over the window. At ``early_exit_threshold`` 1 every
row still takes the last pass: this is what an adaptive exit would save,
not what is saved. None where the program has no such counters."""

from _lib import counters


def read(run):
    c = counters(run)
    if c is None:
        return None
    a, b = (st.get("model_counters") or {} for st in c[:2])
    rows = b.get("exit_rows", 0) - a.get("exit_rows", 0)
    if not rows:
        return None
    passes = sorted(int(k[len("exit_p"):]) for k in b
                    if k.startswith("exit_p"))
    return sum(t * (b[f"exit_p{t}"] - a.get(f"exit_p{t}", 0.0))
               for t in passes) / rows
