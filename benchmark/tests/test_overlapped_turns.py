"""The reader of ``stats()["turns"]``, on made-up ``stats()`` pairs."""

import json
import os

import pytest

from benchmark import run as bench_run

NAME = "overlapped_turn_pct"


def serve_run(open_turns, close_turns):
    def stats(steps, turns):
        return {"steps": steps, **({} if turns is None
                                   else {"turns": turns})}

    return {"raw": {"open": {"stats": stats(50, open_turns), "now": 100.0},
                    "close": {"stats": stats(2450, close_turns),
                              "now": 151.0}}}


def turns(overlapped, drained, surplus_dropped=0):
    return {"overlapped": overlapped, "drained": drained,
            "surplus_dropped": surplus_dropped}


def test_the_share_is_of_the_steps_dispatched_in_the_window(capsys):
    read = bench_run.load_reader(NAME)
    assert read(serve_run(turns(40, 10), turns(2428, 22, 3))) \
        == pytest.approx(100 * 2388 / 2400)
    assert "2388 of 2400 decode steps, 3 ids dropped" \
        in capsys.readouterr().out
    # a speculative engine's steps all start from the host's tokens
    assert read(serve_run(turns(0, 10), turns(0, 510))) == 0.0


@pytest.mark.parametrize("run", [
    serve_run(None, None),                  # the parent's stats()
    serve_run(turns(40, 10), turns(40, 10)),   # no step in the window
    {"raw": {"losses": []}}])               # a train cell
def test_nothing_to_read_gives_no_number(run):
    assert bench_run.load_reader(NAME)(run) is None


def test_the_entries_name_closed_loop_cells():
    """Whichever entries run this reader (a later PR appends
    ``overlapped_turn_pct.<tag>`` for its cell): each lists closed-loop
    cells alone, whose callers wait for their answers."""
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    entries = [m for m in bench["per_layer"]
               if m["name"].split(".")[0] == NAME]
    assert entries
    for entry in entries:
        assert {k: v for k, v in entry.items()
                if k not in ("name", "workloads")} == {
            "unit": "%", "better": "higher", "source": "program_counter",
            "layer": "engine loop: ray_tpu/serve/llm.py",
            "moves": "output_tokens_per_s"}
        assert callable(bench_run.load_reader(entry["name"]))
        for name in entry["workloads"]:
            with open(os.path.join(bench_run.HERE, "traffic",
                                   cells[name]["traffic"] + ".json")) as f:
                assert json.load(f)["kind"] == "closed_loop_handle", name
