"""``layer_metrics/_turn_account.py`` on hand-made events: a capture's two
planes are simulated on two clocks a known offset apart (the arithmetic
needs no chip), in both orders a turn has had: one step ahead of the
host since PR 34 (dispatch N+1, then fetch N) and the order before it
(dispatch N, fetch N)."""

import importlib
import os
import sys

import pytest

FOLDER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "layer_metrics")
sys.path.insert(0, FOLDER)
ta = importlib.import_module("_turn_account")

US = 1e3            # nanoseconds
OFFSET = 1400 * US  # device clock + OFFSET = host clock
STEP, IDS, PREFILL = 10_000 * US, 30 * US, 30_000 * US
LAUNCH = 20 * US


class Capture:
    """The engine's loop against a device, times on the HOST's clock;
    :meth:`events` hands the device's side over on its own."""

    def __init__(self):
        self.t = 50_000 * US        # the host's now
        self.free = 0.0             # when the device has run what it has
        self.spans, self.programs, self.ops = [], [], []
        self.enqueued, self.run_id = {}, 100

    def span(self, name, start, end, step=None):
        self.spans.append((name, start, end - start, step))

    def program(self, name, called_at, dur, split=False):
        """A program the host calls at ``called_at``: the runtime
        enqueues it 300 us later on a thread of its own, and it starts
        when that is done and the device has run what it had."""
        self.run_id += 1
        enq = (called_at + 300 * US, called_at + 350 * US)
        self.enqueued[self.run_id] = enq
        start = max(self.free, enq[1] + LAUNCH)
        self.programs.append((name, start, dur, self.run_id))
        if split:       # two operations and 40 us of nothing between
            self.ops += [(start, dur / 2 - 20 * US),
                         (start + dur / 2 + 20 * US, dur / 2 - 20 * US)]
        else:
            self.ops.append((start, dur))
        self.free = start + dur
        return self.free

    def dispatch(self, step, split=False):
        d0 = self.t
        self.program("jit_step", d0, STEP, split)
        done = self.program("jit_greedy_ids", d0 + 80 * US, IDS)
        self.t = d0 + 500 * US
        self.span("decode_dispatch", d0, self.t, step)
        return done

    def fetch(self, step, done, shift=0):
        f0 = self.t
        self.t = max(self.t, done) + 100 * US
        self.span("logits_fetch", f0, self.t, step + shift)
        self.span("sample", self.t, self.t + 300 * US)
        self.t += 300 * US

    def admit(self, prefill, step):
        a0 = self.t
        if prefill:
            self.program("jit_prefill", a0, PREFILL)
            done = self.program("jit_greedy_ids", a0 + 80 * US, IDS)
            f0 = a0 + 500 * US
            self.t = max(f0, done) + 100 * US
            self.span("prefill_fetch", f0, self.t, step)
            self.span("prefill", a0, self.t, step)
        self.t += 200 * US
        self.span("admit", a0, self.t)

    def run(self, turns, ahead=True, prefill_every=4, shift=0, split=()):
        flight = None
        for k in range(turns):
            t0 = self.t
            done = self.dispatch(k, split=k in split)
            if ahead:
                if flight is not None:
                    self.fetch(k - 1, flight, shift)
                flight = done
            else:
                self.fetch(k, done, shift)
            self.admit(prefill_every and k % prefill_every == 2, k)
            self.span("turn", t0, self.t, k)
            self.t += 5 * US        # between two turns
        return self

    def events(self, **without):
        out = {"ops": [(s - OFFSET, d) for s, d in self.ops],
               "programs": [(n, s - OFFSET, d, r)
                            for n, s, d, r in self.programs],
               "spans": list(self.spans), "enqueued": dict(self.enqueued)}
        out.update(without)
        return out


def test_the_offset_comes_from_the_runs_that_found_the_device_empty():
    ev = Capture().run(41).events()
    gaps = ta.idle_intervals(ev["ops"])
    programs = sorted(ev["programs"], key=lambda p: p[1])
    closers = ta.gap_closers(programs, gaps)
    # the first step of all, and the step after each of ten prefills
    assert [p[0] for p in closers] == ["jit_step"] * 10
    offset, n = ta.clock_offset(programs, ev["enqueued"], gaps)
    assert n == 10 and offset == pytest.approx(OFFSET - LAUNCH)
    # over ALL decode runs the median reads most of a step less: a step
    # enqueued behind another starts when that one ends
    naive = ta.offset_from_all_runs(programs, ev["enqueued"])
    assert naive < OFFSET - 0.8 * STEP
    # under three such runs there is no offset
    few = Capture().run(6, prefill_every=0).events()
    assert ta.clock_offset(sorted(few["programs"], key=lambda p: p[1]),
                           few["enqueued"],
                           ta.idle_intervals(few["ops"])) == (None, 0)


@pytest.mark.parametrize("ahead", [True, False],
                         ids=["one_step_ahead", "dispatch_then_fetch"])
def test_runs_pair_with_the_fetch_of_their_own_step_in_both_orders(ahead):
    ev = Capture().run(41, ahead=ahead).events()
    programs = sorted(ev["programs"], key=lambda p: p[1])
    offset, _ = ta.clock_offset(programs, ev["enqueued"],
                                ta.idle_intervals(ev["ops"]))
    passed, paired, enclosed, joined, why = ta.clock_check(
        programs, ev["enqueued"], ev["spans"], offset)
    # the last step one ahead has no fetch in the capture: not paired
    assert paired == (40 if ahead else 41) and passed == paired
    assert enclosed == paired and joined == len(programs) and not why
    assert ta.account(ev) is not None


@pytest.mark.parametrize("ahead", [True, False],
                         ids=["one_step_ahead", "dispatch_then_fetch"])
def test_fetch_spans_a_step_off_fail_the_check(ahead, capsys):
    ev = Capture().run(41, ahead=ahead, shift=1).events()
    assert ta.account(ev) is None
    out = capsys.readouterr().out
    assert "decode runs ended after their step's fetch" in out
    assert "no reading: the check failed" in out


def test_a_wrong_offset_fails_the_check():
    """The check is of the join, not of the engine: the same events a
    step's length off are refused."""
    ev = Capture().run(41).events()
    programs = sorted(ev["programs"], key=lambda p: p[1])
    passed, paired, *_ = ta.clock_check(programs, ev["enqueued"],
                                        ev["spans"], OFFSET - STEP)
    assert paired == 40 and passed < 0.2 * paired
    # a step late: only the steps whose fetch waited out a prefill pass
    passed, paired, *_ = ta.clock_check(programs, ev["enqueued"],
                                        ev["spans"], OFFSET + STEP)
    assert passed <= 10


def test_the_gaps_after_a_prefill_are_cut_by_phase(capsys):
    ev = Capture().run(41).events()
    out = ta.account(ev)
    steps, prefills = 41, 10
    # the device waits from the end of the prefill's ids program for the
    # fetch's return (100 us), the rest of admit (200), the turn's end
    # and the next one's head (5, under no phase), and the dispatch up
    # to the enqueue's end and the launch (350 + 20)
    assert out["after_prefill"] == pytest.approx(
        prefills * 675 * US / 1e6 / steps)
    assert out["in_turn"] == pytest.approx(0.0, abs=1e-9)
    assert out["unattributed"] == pytest.approx(0.0, abs=1e-9)
    text = capsys.readouterr().out
    assert f"device clock + {(OFFSET - LAUNCH) / 1e3:.1f} us" in text
    assert "100.0% of 40 paired decode runs" in text
    assert "gap 0.675 ms under decode_dispatch (step 3) after prefill" \
        in text
    assert "residue +0.00%" in text
    table, every = ta.cut(
        [gap for gap in ta.idle_intervals(ev["ops"])
         if gap[1] - gap[0] > 1 * US],
        ta.leaf_segments([((n, k), s, d) for n, s, d, k in ev["spans"]]),
        sorted(ev["programs"], key=lambda p: p[1]), OFFSET)
    assert {k: round(v / US) for k, v in table.items()} == {
        ("prefill_fetch", "prefill"): prefills * 100,
        ("admit", "prefill"): prefills * 200,
        ("unattributed", "prefill"): prefills * 5,
        ("decode_dispatch", "prefill"): prefills * 370}
    assert len(every) == prefills


def test_a_turn_that_waits_for_its_own_step_idles_under_its_phases(capsys):
    """The order before PR 34: every step finds the device empty, and
    the gap lies under the fetch's tail, the bookkeeping, the admission
    and the next dispatch; between two turns under no phase."""
    ev = Capture().run(21, ahead=False, prefill_every=0).events()
    out = ta.account(ev)
    gap = (100 + 300 + 200 + 5 + 370) * US
    assert out["in_turn"] == pytest.approx(20 * (gap - 5 * US) / 1e6 / 21)
    assert out["unattributed"] == pytest.approx(20 * 5 * US / 1e6 / 21)
    assert out["after_prefill"] == 0.0
    assert sum(out.values()) == pytest.approx(20 * gap / 1e6 / 21)
    assert "after jit_greedy_ids" in capsys.readouterr().out


def test_a_gap_between_two_operations_of_one_run_is_named_so(capsys):
    ev = Capture().run(41, split={7, 9}).events()
    out = ta.account(ev)
    assert out["in_turn"] == pytest.approx(2 * 40 * US / 1e6 / 41)
    text = capsys.readouterr().out
    assert "in jit_step" in text
    # the modules' durations hold those 80 us and so does the idle time
    assert "0.002 of the idle time lies between two operations" in text


def test_innermost_phase_and_program_before():
    segs = ta.leaf_segments([("turn", 0, 100), ("admit", 10, 50),
                             ("prefill", 20, 30), ("sample", 70, 10)])
    assert segs == [(0, 10, "turn"), (10, 20, "admit"), (20, 50, "prefill"),
                    (50, 60, "admit"), (60, 70, "turn"), (70, 80, "sample"),
                    (80, 100, "turn")]
    programs = [("jit_step", 0, 10, 1), ("jit_greedy_ids", 10, 1, 2),
                ("jit_prefill", 20, 30, 3), ("jit_sample_ids", 50, 1, 4),
                ("jit_merge_ids", 60, 1, 5)]
    starts = [p[1] for p in programs]
    assert ta.label_before(programs, starts, -2, -1) == "nothing"
    assert ta.label_before(programs, starts, 5, 6) == "in jit_step"
    assert ta.label_before(programs, starts, 11, 20) == "jit_greedy_ids"
    # an event's times are rounded: a gap that opens a nanosecond before
    # its run ends and closes after it is a gap AFTER the run
    assert ta.label_before(programs, starts, 9.9, 10) == "in jit_step"
    assert ta.label_before(programs, starts, 9.9, 12) == "jit_step"
    assert ta.label_before(programs, starts, 50.9, 60) == "prefill"
    assert ta.label_before(programs, starts, 51, 60) == "prefill"
    assert ta.label_before(programs[:3], starts[:3], 50, 60) == "prefill"
    assert ta.label_before(programs, starts, 61, 70) == "jit_merge_ids"


@pytest.mark.parametrize("without, why", [
    ({"spans": []}, "no rt.engine.* span"),
    ({"enqueued": {}}, "no DoEnqueueProgram"),
    ({"ops": []}, "no operation"),
    ({"programs": []}, "no operation or no run of the decode program")])
def test_a_capture_that_lacks_a_part_gives_no_number(without, why, capsys):
    ev = Capture().run(21).events(**without)
    assert ta.account(ev) is None
    assert why in capsys.readouterr().out


def test_a_run_without_a_capture_file_gives_no_number():
    for run in ({"trace": {}}, {"trace": {"xplane": "/no/such/file"}},
                {"trace": None}):
        assert ta.read(run, "after_prefill") is None
        assert ta.read(run, "in_turn") is None
