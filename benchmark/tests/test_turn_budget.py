"""The readers of the engine's own account of a turn, on made-up
``stats()`` pairs: ``_turn_budget`` (the phases' rows), ``_phase_walls``
(the distributions), ``prompts_per_admitting_turn`` (the admissions) and
``other_programs_dev_ms`` (the reduced trace's programs)."""

import bisect
import json
import os

import pytest

from benchmark import run as bench_run

EDGES = [16e-6 * 2.0 ** (i / 16) for i in range(18 * 16 + 1)]


def row(count, wall, self_wall=None):
    self_wall = wall if self_wall is None else self_wall
    return [count, wall, self_wall, self_wall / 8, self_wall / 16]


def serve_run(open_stats, close_stats, seconds=51.0):
    return {"raw": {"open": {"stats": open_stats, "now": 100.0},
                    "close": {"stats": close_stats, "now": 100.0 + seconds},
                    "final": {"stats": close_stats}}}


def reading(name, run):
    return bench_run.load_reader(name)(run)


class TestTurnBudget:
    # 1,000 steps in 20 s of turns: 12 s waiting for the step, 3 s for
    # prefills, the rest the thread's own
    A = {"turn": row(10, 1.0, 0.01), "logits_fetch": row(10, 0.5),
         "prefill": row(2, 0.2, 0.02), "prefill_fetch": row(2, 0.18),
         "admit": row(10, 0.3, 0.1), "sample": row(10, 0.1)}
    B = {"turn": row(1010, 21.0, 0.21), "logits_fetch": row(1010, 12.5),
         "prefill": row(102, 3.6, 0.42), "prefill_fetch": row(102, 3.18),
         "admit": row(1010, 5.3, 1.7), "sample": row(1010, 2.1),
         "idle_wait": row(3, 0.006)}

    def _run(self, a=None, b=None):
        return serve_run({"steps": 50, "phases": a or self.A},
                         {"steps": 1050, "phases": b or self.B}, 20.2)

    def test_the_three_sum_to_the_turn(self, capsys):
        run = self._run()
        wait = reading("turn_decode_wait_ms", run)
        prefill = reading("turn_prefill_wait_ms", run)
        host = reading("turn_host_ms", run)
        assert wait == pytest.approx(12.0)
        assert prefill == pytest.approx(3.0)
        assert host == pytest.approx(5.0)
        assert wait + prefill + host == pytest.approx(20.0)
        assert reading("turn_host_ms.chat", run) == host
        # within 1% of the window over its steps, which holds what lies
        # between two turns too
        assert reading("engine_step_ms", run) == pytest.approx(20.2)
        out = capsys.readouterr().out
        assert out.count("[turn_budget]") == 1      # once a run
        assert "decode_wait 12.000 + prefill_wait 3.000 + host 5.000 = " \
               "turn 20.000" in out
        assert "engine_step_ms 20.200" in out
        assert "logits_fetch 12.000" in out and "admit 1.600" in out

    def test_the_block_turn_and_the_speculative_turn_wait_too(self):
        b = dict(self.B, block_fetch=row(100, 1.0), spec_fetch=row(50, 0.5))
        b["turn"] = row(1010, 22.5, 0.21)
        run = self._run(b=b)
        assert reading("turn_decode_wait_ms", run) == pytest.approx(13.5)
        assert reading("turn_host_ms", run) == pytest.approx(5.0)

    def test_a_program_without_prefill_fetch_tells_no_wait_from_work(
            self, capsys):
        """The parent's phases: ``prefill`` holds the dispatch and the
        wait. The prefill's wait reads None, not 0, and so does the
        host's share, which would hold it."""
        a = {k: v for k, v in self.A.items() if k != "prefill_fetch"}
        b = {k: v for k, v in self.B.items() if k != "prefill_fetch"}
        run = self._run(a, b)
        assert reading("turn_decode_wait_ms", run) == pytest.approx(12.0)
        assert reading("turn_prefill_wait_ms", run) is None
        assert reading("turn_host_ms", run) is None
        assert "no prefill_fetch row" in capsys.readouterr().out

    def test_a_run_without_phases_or_steps_gives_no_number(self):
        for run in (serve_run({"steps": 5}, {"steps": 9}),
                    serve_run({"steps": 5, "phases": self.A},
                              {"steps": 5, "phases": self.B}),
                    {"raw": {"losses": []}}):
            for name in ("turn_decode_wait_ms", "turn_prefill_wait_ms",
                         "turn_host_ms"):
                assert reading(name, run) is None


def counts_of(walls):
    counts = [0] * (len(EDGES) + 1)
    for wall in walls:
        counts[bisect.bisect_right(EDGES, wall)] += 1
    return counts


def walls_stats(steps, turn, fetch, picked, delivery=None):
    st = {"steps": steps,
          "phase_walls": {"edges_s": EDGES, "counts": {
              "turn": counts_of(turn), "logits_fetch": counts_of(fetch)}}}
    if delivery is not None:
        st["delivery"] = dict(delivery,
                              pickup_wall_counts=counts_of(picked))
    return st


class TestPhaseWalls:
    BEFORE = dict(turn=[0.3] * 7, fetch=[0.3] * 7, picked=[0.3] * 7)
    # 1,000 turns of 12 ms, one in ten of 40: the median is a plain
    # turn, the 95th percentile an admitting one
    TURN = [0.012] * 900 + [0.040] * 100
    FETCH = [0.010] * 940 + [0.0001] * 60
    PICKED = [0.0005 * (i % 10) + 0.0001 for i in range(1000)]

    def _run(self):
        a = walls_stats(10, delivery={"polls": 10, "polls_empty": 3,
                                      "tokens_picked": 7}, **self.BEFORE)
        b = walls_stats(
            1010, self.BEFORE["turn"] + self.TURN,
            self.BEFORE["fetch"] + self.FETCH,
            self.BEFORE["picked"] + self.PICKED,
            delivery={"polls": 3010, "polls_empty": 2003,
                      "tokens_picked": 1107})
        return serve_run(a, b)

    def test_percentiles_of_the_windows_own_entries(self, capsys):
        run = self._run()
        assert reading("engine_turn_p95_ms", run) == pytest.approx(
            40.0, rel=0.05)
        assert reading("token_pickup_lag_p95_ms", run) == pytest.approx(
            4.6, rel=0.05)
        out = capsys.readouterr().out
        assert "[phase_walls] turn: p95" in out and "over 1000" in out
        assert "median 12." in out or "median 11." in out
        assert "3000 polls, 2000 found nothing (1.82 a token picked), " \
               "1100 tokens picked" in out

    def test_the_share_of_fetches_that_found_the_step_done(self, capsys):
        assert reading("host_bound_turn_pct", self._run()) \
            == pytest.approx(6.0)
        # the edge nearest to a quarter of a millisecond
        assert "under 0.245 ms" in capsys.readouterr().out

    def test_a_program_without_distributions_gives_no_number(self):
        bare = serve_run({"steps": 5}, {"steps": 9})
        no_delivery = serve_run(
            walls_stats(10, [0.01], [0.01], []),
            walls_stats(20, [0.01] * 9, [0.01] * 9, []))
        for name in ("engine_turn_p95_ms", "host_bound_turn_pct",
                     "token_pickup_lag_p95_ms"):
            assert reading(name, bare) is None
            assert reading(name, {"raw": {"losses": []}}) is None
        assert reading("token_pickup_lag_p95_ms", no_delivery) is None
        assert reading("engine_turn_p95_ms", no_delivery) == pytest.approx(
            10.0, rel=0.05)
        # nothing entered the phase inside the window
        same = walls_stats(10, [0.01], [0.01], [])
        assert reading("engine_turn_p95_ms", serve_run(same, same)) is None


class TestAdmissions:
    def test_prompts_a_turn_that_admits(self, capsys):
        a = {"prefills": 10, "prompt_tokens": 1000, "padded_tokens": 1280,
             "turns_admitting": 10, "also_waiting": 0}
        b = {"prefills": 130, "prompt_tokens": 31000,
             "padded_tokens": 41280, "turns_admitting": 90,
             "also_waiting": 300}
        run = serve_run({"steps": 0, "admissions": a},
                        {"steps": 240, "admissions": b})
        assert reading("prompts_per_admitting_turn", run) \
            == pytest.approx(1.5)
        out = capsys.readouterr().out
        assert "120 prefills in 80 turns of 240 decode steps (0.500 a " \
               "step)" in out
        assert "2.50 requests still waiting behind each" in out
        assert "25.0% padding" in out

    def test_no_admission_or_no_counts_give_no_number(self):
        a = {"prefills": 10, "prompt_tokens": 1000, "padded_tokens": 1280,
             "turns_admitting": 10, "also_waiting": 0}
        assert reading("prompts_per_admitting_turn", serve_run(
            {"steps": 0, "admissions": a},
            {"steps": 9, "admissions": a})) is None
        assert reading("prompts_per_admitting_turn", serve_run(
            {"steps": 0}, {"steps": 9})) is None


def test_the_other_programs_are_neither_the_step_nor_a_prefill(capsys):
    def program(count, total_s):
        return {"count": count, "total_s": total_s,
                "median_s": total_s / count}

    trace = {"programs": {
        "jit_step": program(200, 2.0), "jit_prefill": program(20, 1.0),
        "jit_greedy_ids": program(220, 0.011),
        "jit_merge_ids": program(20, 0.001),
        "jit_convert_element_type": program(40, 0.0004)}}
    for name in ("other_programs_dev_ms", "other_programs_dev_ms.chat"):
        assert reading(name, {"trace": trace}) == pytest.approx(0.062)
    out = capsys.readouterr().out
    assert "jit_greedy_ids 0.0550 (220 runs, median 50.0 us)" in out
    # a block turn's steps are block steps, its deciding program another
    trace = {"programs": {"jit_block_step": program(100, 2.0),
                          "jit_block_decide": program(100, 0.05)}}
    assert reading("other_programs_dev_ms", {"trace": trace}) \
        == pytest.approx(0.5)
    assert reading("other_programs_dev_ms", {"trace": {"programs": {
        "jit_train_step": program(3, 6.0)}}}) is None


def test_the_twelve_entries_of_the_turns_account_stand_as_asked():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    closed = [w["name"] for w in bench["workloads"]
              if w["name"].startswith("serve-")
              and w["name"] != "serve-chat-steady"]
    assert len(closed) == 7
    engine = "engine loop: ray_tpu/serve/llm.py"
    want = {
        "turn_decode_wait_ms": (closed, "program_counter", engine, "ms"),
        "turn_prefill_wait_ms": (closed, "program_counter", engine, "ms"),
        "turn_host_ms": (closed, "program_counter", engine, "ms"),
        "prompts_per_admitting_turn": (closed, "program_counter", engine,
                                       "prompts"),
        "gap_after_prefill_ms": (closed, "program_span", engine, "ms"),
        "gap_in_turn_ms": (closed, "program_span", engine, "ms"),
        "other_programs_dev_ms": (closed, "device_trace", "device", "ms"),
        "other_programs_dev_ms.chat": (["serve-chat-steady"],
                                       "device_trace", "device", "ms"),
        "engine_turn_p95_ms": (["serve-chat-steady"], "program_counter",
                               engine, "ms"),
        "host_bound_turn_pct": (["serve-chat-steady"], "program_counter",
                                engine, "%"),
        "token_pickup_lag_p95_ms": (["serve-chat-steady"],
                                    "program_counter", engine, "ms"),
        "turn_host_ms.chat": (["serve-chat-steady"], "program_counter",
                              engine, "ms")}
    for name, (cells, source, layer, unit) in want.items():
        m = by_name[name]
        assert (m["workloads"], m["source"], m["layer"], m["unit"]) \
            == (cells, source, layer, unit), name
        assert m["better"] == ("higher" if name
                               == "prompts_per_admitting_turn" else "lower")
        assert m["moves"] == ("itl_p95_ms" if cells
                              == ["serve-chat-steady"]
                              else "output_tokens_per_s")
    assert not [n for n in by_name if n.startswith(("idle_", "dev_idle_"))]
