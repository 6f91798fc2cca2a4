"""The whole of ``BENCHMARK.json``'s ``per_layer``, entry by entry, as
the files stand: whatever a later PR appends is held to the same, and
nothing here counts entries or knows a position in the list."""

import json
import os

import pytest

from benchmark import fold
from benchmark import run as bench_run

FOLDER = os.path.join(bench_run.HERE, "layer_metrics")
with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m.get("workloads", CELLS) for m in BENCH["end_to_end"]}
CAP = fold.CAP  # the contract's


def resolved(name):
    """(the ``.py`` an entry's name ends at, the arguments it is called
    with) through however many alias files: the fold's own rule."""
    alias = fold.alias(bench_run.ROOT, name)
    if alias is None:
        assert os.path.exists(os.path.join(FOLDER, name + ".py")), name
    else:
        assert set(alias) <= {"reader", "args"}, name
    return fold.resolved(bench_run.ROOT, name)


def test_the_list_is_within_the_contracts_cap_and_names_each_metric_once():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert 0 < len(names) <= CAP
    assert len(names) == len(set(names))
    assert not set(names) & set(E2E)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_an_entry_has_a_reader_and_cells_that_report_what_it_moves(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert callable(bench_run.load_reader(m["name"]))
    assert m["moves"] in E2E
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    if "workloads" not in m:
        return      # reported wherever the metric it moves is
    assert m["workloads"] and len(set(m["workloads"])) == len(m["workloads"])
    for cell in m["workloads"]:
        assert cell in CELLS, f"{m['name']} lists {cell!r}: no such cell"
        assert cell in E2E[m["moves"]], \
            f"{cell} does not report {m['moves']}, which {m['name']} moves"
    # in the order the cells stand in
    assert m["workloads"] == [c for c in CELLS if c in m["workloads"]]
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%" and m["better"] == "higher"


def test_copies_of_a_reader_differ_by_a_tag_alone():
    """Two entries that run one reader with the same arguments and agree
    in ``moves``, ``unit``, ``better`` and ``source`` are copies. A PR
    that adds a cell may append ``<metric>.<tag>`` (it may edit no
    entry's ``workloads``); a ``benchmark`` PR folds them (README, "the
    fold": ``benchmark/fold.py``). So copies share their name up to the
    tag, and at most one of them has none."""
    seen = {}
    for m in BENCH["per_layer"]:
        reader, args = resolved(m["name"])
        key = (reader, json.dumps(args, sort_keys=True), m["moves"],
               m["unit"], m["better"], m["source"])
        seen.setdefault(key, []).append(m["name"])
    for names in seen.values():
        assert len({n.split(".")[0] for n in names}) == 1, names
        assert sum("." not in n for n in names) <= 1, names


def test_every_metric_file_has_an_entry_and_every_shared_one_a_user():
    names = {m["name"] for m in BENCH["per_layer"]}
    used = set()
    for entry in os.listdir(FOLDER):
        stem, ext = os.path.splitext(entry)
        if ext not in (".py", ".json") or stem.startswith("_"):
            continue
        assert stem in names, f"layer_metrics/{entry} has no entry"
    for name in names:
        path = os.path.join(FOLDER, name + ".json")
        while os.path.exists(path):
            with open(path) as f:
                reader = json.load(f)["reader"]
            used.add(reader)
            path = os.path.join(FOLDER, reader + ".json")
    shared = {e[:-3] for e in os.listdir(FOLDER)
              if e.startswith("_") and e.endswith(".py")} - {"_lib"}
    assert shared <= used, sorted(shared - used)


def test_none_of_the_idle_split_names_is_left():
    names = {m["name"] for m in BENCH["per_layer"]}
    gone = {f"{prefix}_{part}_ms.{tag}"
            for prefix, tags in (("idle", ("chat", "batch")),
                                 ("dev_idle", ("moe", "mla", "whole")))
            for part in ("sample", "fetch", "other_host", "unattributed")
            for tag in tags}
    assert len(gone) == 20 and not gone & names
    assert not os.path.exists(os.path.join(FOLDER, "_idle_by_span.py"))
