"""The fold of ``BENCHMARK.json``'s ``per_layer`` (README, "The fold").

    python3 benchmark/fold.py            # prints the groups of copies
    python3 benchmark/fold.py --write    # and folds them

A PR that adds a cell may edit no entry that is there, so it appends
``<metric>.<tag>`` copies; the list holds 128 at the most, so a
``benchmark`` PR folds them when a configuration has come. Entries whose
names resolve, through their alias files, to the same ``.py`` with the
same arguments, and that agree in ``moves``, ``unit``, ``better`` and
``source``, are copies. A group becomes ONE entry, where its first
stood and under the name of its first (the one accepted first: the name
without a tag where the group has it, else the group's oldest tag, as
``prefill_dev_share_pct.routed``, so that no cell's oldest name moves),
its ``workloads`` the union in ``BENCHMARK.json``'s cell order; the
other entries go, and their alias files with them. No reader changes,
and every other key of ``BENCHMARK.json`` is written back as it was
read. Pure Python (no jax).
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CAP = 128       # the contract's
SERVING = "serving programs: "


def folder(root: str) -> str:
    return os.path.join(root, "benchmark", "layer_metrics")


def alias(root: str, name: str):
    """What ``layer_metrics/<name>.json`` says, None where the name is a
    reader's own (``<name>.py``)."""
    path = os.path.join(folder(root), name + ".json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def resolved(root: str, name: str):
    """(the ``.py`` a name ends at, the arguments it is called with)
    through however many alias files, as ``run.load_reader`` calls it."""
    found = alias(root, name)
    if found is None:
        return name, {}
    reader, args = resolved(root, found["reader"])
    return reader, dict(args, **found.get("args", {}))


def groups(root: str, per_layer: list) -> list:
    """The groups of two or more copies, each in the list's order."""
    seen = {}
    for m in per_layer:
        reader, args = resolved(root, m["name"])
        key = (reader, json.dumps(args, sort_keys=True), m["moves"],
               m["unit"], m["better"], m["source"])
        seen.setdefault(key, []).append(m)
    return [g for g in seen.values() if len(g) > 1]


def folded(root: str, group: list, cells: list) -> dict:
    """The one entry a group becomes."""
    # a reader's own file cannot go, so its name is the group's
    keep = next((m for m in group if alias(root, m["name"]) is None),
                group[0])
    layers = {m["layer"] for m in group}
    if len(layers) == 1:
        layer = keep["layer"]
    elif all(x.startswith(SERVING) for x in layers):
        layer = SERVING + "ray_tpu/models/serving.py"
    else:
        raise SystemExit(f"{[m['name'] for m in group]} are copies but "
                         f"stand in layers {sorted(layers)}: name one")
    out = dict(keep, layer=layer)
    if all("workloads" in m for m in group):
        listed = {c for m in group for c in m["workloads"]}
        out["workloads"] = [c for c in cells if c in listed]
    else:       # one of them is reported wherever `moves` is: so is this
        out.pop("workloads", None)
    return out


def fold(root: str, bench: dict):
    """(the folded ``per_layer``, {a folded entry's name: the names that
    went into it, whose alias files go})."""
    cells = [w["name"] for w in bench["workloads"]]
    first, others, went = {}, set(), {}
    for group in groups(root, bench["per_layer"]):
        one = folded(root, group, cells)
        first[group[0]["name"]] = one
        others |= {m["name"] for m in group[1:]}
        went[one["name"]] = [m["name"] for m in group
                             if m["name"] != one["name"]]
    per_layer = [first.get(m["name"], m) for m in bench["per_layer"]
                 if m["name"] not in others]
    gone = {name for names in went.values() for name in names}
    # an alias file that stays may read none that goes
    for entry in sorted(os.listdir(folder(root))):
        stem, ext = os.path.splitext(entry)
        if ext == ".json" and stem not in gone:
            reader = alias(root, stem)["reader"]
            if reader in gone:
                raise SystemExit(
                    f"layer_metrics/{entry} reads {reader!r}, which the "
                    "fold would delete: point it at the reader")
    return per_layer, went


def main() -> int:
    options = sys.argv[1:]
    if options not in ([], ["--write"]):
        raise SystemExit(__doc__.split("\n\n")[1])
    root = os.path.dirname(HERE)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = len(bench["per_layer"])
    bench["per_layer"], went = fold(root, bench)
    for m in bench["per_layer"]:
        if m["name"] in went:
            print(f"{m['name']} <- {', '.join(went[m['name']])}: "
                  f"{', '.join(m.get('workloads', ['every cell']))}")
    print(f"{before} entries, {len(went)} groups of copies: "
          f"{len(bench['per_layer'])} of the cap of {CAP} after the fold")
    if options and went:
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            f.write(json.dumps(bench, indent=1) + "\n")
        gone = [name for names in went.values() for name in names]
        for name in gone:
            os.remove(os.path.join(folder(root), name + ".json"))
        print(f"wrote BENCHMARK.json and deleted {len(gone)} alias files")
    return 0


if __name__ == "__main__":
    sys.exit(main())
