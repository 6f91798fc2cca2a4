"""Blocking prefill programs the engine ran for each turn that ran at
least one: ``stats()["admissions"]`` across the window, ``prefills`` over
``turns_admitting``. 1.0 means no turn admitted two; what several waiting
prompts in ONE prefill could batch is printed beside it (the requests
still waiting behind each one picked, a prefill) with the share of the
prefills' rows that were padding (the buckets' sizes against the
prompts'). None where the program keeps no such counts or nothing was
admitted in the window."""

from _lib import counters


def read(run):
    c = counters(run)
    if c is None or "admissions" not in c[0] or "admissions" not in c[1]:
        return None
    d = {k: c[1]["admissions"][k] - c[0]["admissions"][k]
         for k in c[1]["admissions"]}
    if not d["turns_admitting"]:
        return None
    steps = c[1]["steps"] - c[0]["steps"]
    print(f"[admissions] {d['prefills']} prefills in "
          f"{d['turns_admitting']} turns of {steps} decode steps "
          f"({d['prefills'] / max(steps, 1):.3f} a step), "
          f"{d['also_waiting'] / d['prefills']:.2f} requests still waiting "
          f"behind each, {d['prompt_tokens']} prompt tokens in "
          f"{d['padded_tokens']} rows: "
          f"{100 * (1 - d['prompt_tokens'] / d['padded_tokens']):.1f}% "
          "padding", flush=True)
    return d["prefills"] / d["turns_admitting"]
