"""``benchmark/fold.py`` (README, "The fold"): on the committed list it
finds nothing to fold; on a copy of the tree in which a next cell has
appended its tagged copies, as a PR that adds a cell must, it takes them
back; and its rule, case by case, on a list made by hand. Nothing here
knows how many entries there are or what the folded ones are called."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import fold
from benchmark import run as bench_run

ROOT = bench_run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def _fold_py(root, *options):
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "fold.py"),
         *options], capture_output=True, text=True, timeout=120)


# ------------------------------------------------------ the committed tree
def test_the_committed_list_holds_no_copies():
    assert fold.groups(ROOT, BENCH["per_layer"]) == []
    proc = _fold_py(ROOT)
    assert proc.returncode == 0, proc.stderr
    n = len(BENCH["per_layer"])
    assert proc.stdout == (f"{n} entries, 0 groups of copies: {n} of the "
                           f"cap of {fold.CAP} after the fold\n")


def test_it_imports_no_jax_and_takes_one_option():
    code = ("import sys; sys.path.insert(0, %r); from benchmark import fold;"
            " assert 'jax' not in sys.modules and 'numpy' not in sys.modules"
            % ROOT)
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0
    for options in (["--help"], ["--write", "--dry"], ["--keep", "x"]):
        proc = _fold_py(ROOT, *options)
        assert proc.returncode != 0 and "fold.py --write" in proc.stderr


# -------------------------------------- the next cell's copies, taken back
def _tree_with_the_copies_of(tmp_path, cell):
    """A copy of the list and the metric files in which ``next-cell``
    reports all that ``cell`` does, the way a PR that adds a cell has to
    (README, step 3): ``<metric>.next`` entries and their alias files."""
    os.makedirs(tmp_path / "benchmark")
    shutil.copy(os.path.join(ROOT, "benchmark", "fold.py"),
                tmp_path / "benchmark")
    shutil.copytree(fold.folder(ROOT), fold.folder(tmp_path),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(dict(
        next(w for w in bench["workloads"] if w["name"] == cell),
        name="next-cell"))
    for m in bench["end_to_end"]:
        if cell in m.get("workloads", ()):
            m["workloads"].append("next-cell")
    brought = []
    for m in BENCH["per_layer"]:
        if cell not in m.get("workloads", ()):
            continue
        name = m["name"].split(".")[0] + ".next"
        assert name not in brought, name
        brought.append(name)
        bench["per_layer"].append(dict(m, name=name, workloads=["next-cell"]))
        with open(os.path.join(fold.folder(tmp_path), name + ".json"),
                  "w") as f:
            json.dump(fold.alias(ROOT, m["name"]) or {"reader": m["name"]}, f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return bench, brought


@pytest.mark.parametrize("cell", CELLS)
def test_the_copies_a_next_cell_brings_are_folded_into_the_entries_it_copied(
        tmp_path, cell):
    before, brought = _tree_with_the_copies_of(tmp_path, cell)
    assert brought
    found = _fold_py(tmp_path)
    assert found.returncode == 0, found.stderr
    assert f"{len(brought)} groups of copies" in found.stdout
    with open(tmp_path / "BENCHMARK.json") as f:
        assert json.load(f) == before           # without --write: a look
    proc = _fold_py(tmp_path, "--write")
    assert proc.returncode == 0, proc.stderr
    assert f"deleted {len(brought)} alias files" in proc.stdout
    with open(tmp_path / "BENCHMARK.json") as f:
        after = json.load(f)
    for key in before:
        if key != "per_layer":
            assert after[key] == before[key], key
    # the list is the committed one, entry by entry, but that whatever
    # listed the cell lists the next cell too, last as it stands last
    assert len(after["per_layer"]) == len(BENCH["per_layer"])
    for was, now in zip(BENCH["per_layer"], after["per_layer"]):
        if cell in was.get("workloads", ()):
            was = dict(was, workloads=was["workloads"] + ["next-cell"])
        assert now == was
    left = os.listdir(fold.folder(tmp_path))
    assert not [f for f in left if ".next." in f]
    assert sorted(left) == sorted(
        f for f in os.listdir(fold.folder(ROOT)) if f != "__pycache__")
    assert "0 groups of copies" in _fold_py(tmp_path).stdout


# ------------------------------------------------- the rule, case by case
def _tiny(tmp_path, entries, files):
    """A list of three cells; ``files``: {alias name: what it says}, and
    one reader of its own, ``r.py``."""
    os.makedirs(fold.folder(tmp_path))
    with open(os.path.join(fold.folder(tmp_path), "r.py"), "w") as f:
        f.write("def read(run, **args):\n    return None\n")
    for name, says in files.items():
        with open(os.path.join(fold.folder(tmp_path), name + ".json"),
                  "w") as f:
            json.dump(says, f)
    return {"workloads": [{"name": c} for c in "abc"],
            "per_layer": [dict({"unit": "ms", "better": "lower",
                                "source": "device_trace", "layer": "device",
                                "moves": "tokens_per_s"}, **e)
                          for e in entries]}


def test_a_group_is_one_entry_where_its_first_stood_in_the_cells_order(
        tmp_path):
    bench = _tiny(tmp_path, [
        {"name": "r", "workloads": ["b"]},
        {"name": "other", "workloads": ["a"]},
        {"name": "r.x", "workloads": ["c"]},
        {"name": "r.y", "workloads": ["a"]}],
        {"r.x": {"reader": "r"}, "r.y": {"reader": "r.x"},
         "other": {"reader": "r", "args": {"what": "else"}}})
    per_layer, went = fold.fold(tmp_path, bench)
    assert [m["name"] for m in per_layer] == ["r", "other"]
    assert per_layer[0]["workloads"] == ["a", "b", "c"]
    assert per_layer[1] == bench["per_layer"][1]
    assert went == {"r": ["r.x", "r.y"]}


def test_an_alias_whose_arguments_differ_by_a_character_is_no_copy(tmp_path):
    bench = _tiny(tmp_path, [
        {"name": "m", "workloads": ["a"]},
        {"name": "m.x", "workloads": ["b"]},
        {"name": "m.y", "workloads": ["c"], "moves": "itl_p95_ms"}],
        {"m": {"reader": "r", "args": {"program": "^jit_step"}},
         "m.x": {"reader": "r", "args": {"program": "^jit_step$"}},
         "m.y": {"reader": "r", "args": {"program": "^jit_step"}}})
    assert fold.groups(tmp_path, bench["per_layer"]) == []
    assert fold.fold(tmp_path, bench) == (bench["per_layer"], {})


def test_a_group_that_has_no_name_without_a_tag_keeps_its_oldest_tag(
        tmp_path):
    """As ``prefill_dev_share_pct.routed``: the name without a tag is
    another entry's, which moves another metric."""
    bench = _tiny(tmp_path, [
        {"name": "m", "workloads": ["a"], "moves": "itl_p95_ms"},
        {"name": "m.routed", "workloads": ["c"]},
        {"name": "m.bd", "workloads": ["b"]}],
        {name: {"reader": "r"} for name in ("m", "m.routed", "m.bd")})
    per_layer, went = fold.fold(tmp_path, bench)
    assert [m["name"] for m in per_layer] == ["m", "m.routed"]
    assert per_layer[1]["workloads"] == ["b", "c"]
    assert went == {"m.routed": ["m.bd"]}


def test_a_readers_own_file_is_never_the_one_to_go(tmp_path):
    bench = _tiny(tmp_path, [{"name": "r.x", "workloads": ["a"]},
                             {"name": "r", "workloads": ["b"]}],
                  {"r.x": {"reader": "r"}})
    per_layer, went = fold.fold(tmp_path, bench)
    assert [m["name"] for m in per_layer] == ["r"] and went == {"r": ["r.x"]}
    assert per_layer[0]["workloads"] == ["a", "b"]


def test_a_copy_of_an_entry_that_lists_no_cells_adds_none(tmp_path):
    bench = _tiny(tmp_path, [{"name": "r"},
                             {"name": "r.x", "workloads": ["b"]}],
                  {"r.x": {"reader": "r"}})
    per_layer, went = fold.fold(tmp_path, bench)
    assert per_layer == [bench["per_layer"][0]] and went == {"r": ["r.x"]}


def test_layers_that_differ_by_the_models_file_become_the_serving_programs(
        tmp_path):
    files = {"m": {"reader": "r"}, "m.x": {"reader": "r"}}
    bench = _tiny(tmp_path, [
        {"name": "m", "workloads": ["a"],
         "layer": "serving programs: ray_tpu/models/paged_cache.py"},
        {"name": "m.x", "workloads": ["b"],
         "layer": "serving programs: ray_tpu/models/mla.py"}], files)
    per_layer, _ = fold.fold(tmp_path, bench)
    assert per_layer[0]["layer"] \
        == "serving programs: ray_tpu/models/serving.py"
    bench["per_layer"][1]["layer"] = "engine loop: ray_tpu/serve/llm.py"
    with pytest.raises(SystemExit, match="stand in layers"):
        fold.fold(tmp_path, bench)


def test_an_alias_that_stays_may_not_read_one_that_goes(tmp_path):
    bench = _tiny(tmp_path, [
        {"name": "m", "workloads": ["a"]},
        {"name": "m.x", "workloads": ["b"]},
        {"name": "n", "workloads": ["c"]}],
        {"m": {"reader": "r"}, "m.x": {"reader": "r"},
         "n": {"reader": "m.x", "args": {"what": "else"}}})
    with pytest.raises(SystemExit, match="n.json reads 'm.x'"):
        fold.fold(tmp_path, bench)
