"""The engine loop's own clock: named phases (``stats()["phases"]`` and
spans in a profiler capture), per-request lifecycle records
(``stats()["requests"]``), and the stable names of the device side
(``jit_train_step``, the Pallas kernels). CPU, debug model."""

import ast
import bisect
import functools
import glob
import importlib.util
import inspect
import os
import subprocess
import sys
import threading
import time

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import llama
from ray_tpu.serve.llm import LLMEngine, LLMServer
from ray_tpu.util import profiling

CFG = llama.CONFIGS["debug"]
# the phases of the table in PERF.md section 3 that the plain path reaches
PLAIN = {"turn", "window_free", "grow", "admit", "prefill",
         "prefill_fetch", "decode_dispatch", "logits_fetch", "sample",
         "idle_wait"}
ENQ, ADM, FIRST, PICKED, FIN, PLEN, OLEN, PREEMPT, STATUS = range(9)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0))


def _engine(params, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq", 64)
    return LLMEngine(config=CFG, params=params, kv_cache="paged", **kw)


def _stream(eng, prompt, n, **kw):
    rid = eng.submit(prompt, n, **kw)
    out = []
    while True:
        st = eng.poll(rid)
        out.extend(st["chunks"])
        if st["done"]:
            return out
        time.sleep(0.002)


@pytest.fixture(scope="module")
def ran(params):
    """A few streamed and a few blocking requests; the engine is joined
    before the last stats(), so no phase is open in the snapshot."""
    eng = _engine(params)
    try:
        eng.generate([9, 9], 2)                 # compile outside the diff
        before = eng.stats()
        streamed = [_stream(eng, [5, 17, 99], 6, temperature=0.7),
                    _stream(eng, [7, 7], 5)]
        blocking = [eng.generate([1, 2, 3, 4, 5, 6], 6),
                    eng.generate([100], 4)]
    finally:
        eng.shutdown()
    return {"before": before, "after": eng.stats(), "streamed": streamed,
            "blocking": blocking,
            "buckets": [eng._prompt_pad(n) for n in (3, 2, 6, 1)]}


class TestPhases:
    def test_every_plain_phase_has_a_row(self, ran):
        rows = ran["after"]["phases"]
        assert PLAIN <= set(rows), sorted(rows)
        for name in PLAIN:
            count, wall, self_wall, timed_wall, timed_cpu = rows[name]
            assert count >= 1 and wall > 0.0
            assert 0.0 <= self_wall <= wall + 1e-9
            # the CPU clock is read in one turn of CPU_EVERY
            assert 0.0 <= timed_wall <= self_wall + 1e-9
            assert timed_cpu >= 0.0
        assert 0.0 < rows["sample"][3] < rows["sample"][2]

    def test_dispatch_and_fetch_count_the_steps(self, ran):
        a, b = ran["before"], ran["after"]
        steps = b["steps"] - a["steps"]
        assert steps >= 6
        def runs(name):
            return b["phases"][name][0] - a["phases"].get(name, [0])[0]

        assert runs("decode_dispatch") == runs("logits_fetch") == steps
        # the answer that `before` waited for left from inside `sample`,
        # before that phase's first exit had made its row
        assert steps <= runs("sample") <= steps + 1
        # a step is dispatched behind the one the host has not read, or
        # with none in flight (a request's first): each step is one
        turns = {k: b["turns"][k] - a["turns"][k] for k in b["turns"]}
        assert turns["overlapped"] + turns["drained"] == steps
        # four answers one after the other: each one's first step finds
        # the device empty, every other is dispatched ahead of the read
        assert turns == {"overlapped": steps - 4, "drained": 4,
                         "surplus_dropped": 0}

    def test_sampling_counts_the_turns_by_their_program(self, ran):
        a, b = ran["before"]["sampling"], ran["after"]["sampling"]
        # one answer of 6 at a temperature: its first token and 5 turns;
        # then greedy answers of 5, 6 and 4: 4 + 5 + 3 turns
        assert b["sampled_tokens"] - a["sampled_tokens"] == 6
        assert b["sampled_turns"] - a["sampled_turns"] == 5
        assert b["greedy_turns"] - a["greedy_turns"] == 12
        assert (b["greedy_turns"] + b["sampled_turns"]
                == ran["after"]["steps"])

    def test_leaf_self_times_cover_the_turn(self, ran):
        rows = ran["after"]["phases"]
        turn = rows["turn"][1]
        leaves = sum(r[2] for n, r in rows.items() if n != "turn")
        assert leaves <= turn + 1e-9
        assert leaves >= 0.95 * turn, (leaves, turn)
        # a parent's whole time holds its child's
        assert rows["admit"][1] >= rows["prefill"][1]
        assert rows["admit"][2] <= rows["admit"][1] - rows["prefill"][1] \
            + 1e-9
        # the wait for a prefill is a phase of its own inside it
        assert rows["prefill"][1] >= rows["prefill_fetch"][1]
        assert rows["prefill"][2] == pytest.approx(
            rows["prefill"][1] - rows["prefill_fetch"][1])

    def test_every_self_wall_sums_to_the_turns(self, ran):
        """A turn's wall is its phases' self walls and its own: nothing
        of a turn is outside the account (the one step the shutdown
        lands after the last turn is)."""
        rows = ran["after"]["phases"]
        assert sum(r[2] for r in rows.values()) == pytest.approx(
            rows["turn"][1], rel=0.01)

    def test_each_phases_walls_are_counted_once_an_exit(self, ran):
        walls = ran["after"]["phase_walls"]
        assert walls["edges_s"] == list(profiling.WALL_EDGES_S)
        rows = ran["after"]["phases"]
        assert set(walls["counts"]) == set(rows)
        for name, counts in walls["counts"].items():
            assert len(counts) == len(walls["edges_s"]) + 1
            assert sum(counts) == rows[name][0], name
        # a 2 ms sleep lies in the buckets from 2 ms up
        i = bisect.bisect_right(walls["edges_s"], 0.002)
        idle = walls["counts"]["idle_wait"]
        assert sum(idle[:i]) == 0 and sum(idle[i:]) >= 1

    def test_admissions_count_the_prefills_and_their_rows(self, ran):
        a, b = ran["before"], ran["after"]
        adm = {k: b["admissions"][k] - a["admissions"][k]
               for k in b["admissions"]}
        # four answers one after the other: a prefill each, alone
        assert adm["prefills"] == adm["turns_admitting"] == 4
        assert adm["also_waiting"] == 0
        assert adm["prompt_tokens"] == 3 + 2 + 6 + 1
        assert adm["padded_tokens"] == sum(ran["buckets"]) \
            > adm["prompt_tokens"]
        fetches = b["phases"]["prefill_fetch"][0] \
            - a["phases"]["prefill_fetch"][0]
        assert fetches == adm["prefills"]

    def test_delivery_counts_every_token_picked(self, ran):
        a, b = ran["before"]["delivery"], ran["after"]["delivery"]
        picked = b["tokens_picked"] - a["tokens_picked"]
        assert picked == sum(len(out) for out in ran["streamed"]) == 11
        polls = b["polls"] - a["polls"]
        empty = b["polls_empty"] - a["polls_empty"]
        # a poll every 2 ms against the CPU's steps: some found nothing,
        # and each that found something is one wait in the distribution
        assert 0 < empty < polls
        waits = sum(b["pickup_wall_counts"]) - sum(a["pickup_wall_counts"])
        assert waits == polls - empty and 1 <= waits <= picked
        assert len(b["pickup_wall_counts"]) \
            == len(profiling.WALL_EDGES_S) + 1

    def test_stats_keeps_what_it_had(self, ran):
        for key in ("steps", "tokens_generated", "active_slots", "queued",
                    "preemptions", "kv_blocks_free", "kv_blocks_total",
                    "device", "compile_cache", "prefix_cache"):
            assert key in ran["after"], key
        assert ran["after"]["active_slots"] == 0


class TestRequestRecords:
    def test_lifecycle_order_and_pickup(self, ran):
        req = ran["after"]["requests"]
        assert req["finished"] == 5 and len(req["recent"]) == 5
        recent = req["recent"][1:]              # without the compile call
        for r in recent:
            assert r[ENQ] <= r[ADM] <= r[FIRST] <= r[FIN], r
            assert r[STATUS] == "ok" and r[PREEMPT] == 0
        streamed, blocking = recent[:2], recent[2:]
        for r, out in zip(streamed, ran["streamed"]):
            assert r[PICKED] is not None and r[FIRST] <= r[PICKED], r
            assert r[OLEN] == len(out)
        for r, out in zip(blocking, ran["blocking"]):
            assert r[PICKED] is None, r
            assert r[OLEN] == len(out)
        assert [r[PLEN] for r in recent] == [3, 2, 6, 1]

    def test_preemption_shows_in_the_record(self, params):
        # test_paged_cache.py's tight pool: three answers of 40 tokens
        # cannot all grow inside 96 tokens of KV
        eng = _engine(params, num_slots=3, max_seq=256, kv_block_size=16,
                      kv_pool_tokens=96)
        try:
            threads = [threading.Thread(
                target=eng.generate, args=([2 + i, 9, 4],),
                kwargs={"max_tokens": 40, "timeout_s": 180})
                for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            st = eng.stats()
        finally:
            eng.shutdown()
        recent = st["requests"]["recent"]
        assert len(recent) == 3 and all(r[OLEN] == 40 for r in recent)
        assert st["preemptions"] >= 1
        assert sum(r[PREEMPT] for r in recent) == st["preemptions"]
        # a preempted request is prefilled again, prompt and answer so far
        adm = st["admissions"]
        assert adm["prefills"] == 3 + st["preemptions"] \
            == st["phases"]["prefill_fetch"][0]
        assert adm["prompt_tokens"] > 3 * 3
        assert adm["padded_tokens"] >= adm["prompt_tokens"]
        assert 1 <= adm["turns_admitting"] <= adm["prefills"]
        # three at once into three slots: two waited behind the first
        assert adm["also_waiting"] >= 1
        assert st["delivery"]["polls"] == 0         # nobody streamed

    def test_ring_stops_at_512_and_finished_keeps_counting(self, params):
        eng = _engine(params)
        try:
            for i in range(515):
                eng.generate([1 + i % 7, 2], 1)
            req = eng.stats()["requests"]
        finally:
            eng.shutdown()
        assert req["finished"] == 515 and len(req["recent"]) == 512
        enq = [r[ENQ] for r in req["recent"]]
        assert enq == sorted(enq)

    def test_cancelled_and_failed_requests_leave_a_record(self, params):
        eng = _engine(params, num_slots=1)
        try:
            long_rid = eng.submit([1, 2, 3], 40)
            waiting_rid = eng.submit([4, 5], 4)   # behind it: one slot
            assert eng.cancel(waiting_rid) and eng.cancel(long_rid)
            deadline = time.monotonic() + 60
            while (eng.stats()["requests"]["finished"] < 2
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            recent = eng.stats()["requests"]["recent"]
        finally:
            eng.shutdown()
        assert sorted(r[STATUS] for r in recent) == ["cancelled"] * 2
        assert all(r[FIN] >= r[ENQ] for r in recent)


def _server(params, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq", 64)
    return LLMServer(config=CFG, params=params, kv_cache="paged", **kw)


def _device_paced(eng, seconds):
    """The decode step waits as a device would, the interpreter lock
    free: a reader that a landing woke has the time of a step to pick it
    up, as on the chip, whatever else this machine runs."""
    decode = eng._decode

    def paced(*args):
        time.sleep(seconds)
        return decode(*args)

    eng._decode = paced


def _read(srv, out, prompt, n):
    for item in srv.stream(prompt, max_tokens=n):
        out.extend(item if isinstance(item, list) else [item])


STREAMS = [([5, 17, 99], 40), ([6, 17, 99], 40), ([7, 17, 99], 33),
           ([1, 2, 3, 4, 5, 6], 36)]            # the last admitted later


@pytest.fixture(scope="module")
def streamed(params):
    """Three streams at once through ``LLMServer.stream`` and a fourth
    admitted while they run; then the same prompts through generate().
    The net is hung two seconds down, where no step of this model on a
    busy CPU reaches it: what arrives, a wake-up brought."""
    srv = _server(params)
    srv._STREAM_WAIT_S = 2.0
    eng = srv.engine
    try:
        for prompt, _ in STREAMS[::3]:
            eng.generate(prompt, 2)             # compile outside the diff
        _device_paced(eng, 0.005)
        before = eng.stats()["delivery"]
        outs = [[] for _ in STREAMS]
        threads = [threading.Thread(target=_read, args=(srv, out, *job))
                   for out, job in zip(outs, STREAMS)]
        for t in threads[:3]:
            t.start()
        deadline = time.monotonic() + 60
        while not outs[0] and time.monotonic() < deadline:
            time.sleep(0.001)
        threads[3].start()                      # mid-stream
        for t in threads:
            t.join(120)
        alive = [t.is_alive() for t in threads]
        after = eng.stats()["delivery"]
        want = [eng.generate(prompt, n) for prompt, n in STREAMS]
    finally:
        eng.shutdown()
    return {"outs": outs, "want": want, "alive": alive,
            "delivery": {k: after[k] - before[k] for k in before
                         if k != "pickup_wall_counts"}}


class TestAStreamedTokenWakesItsReader:
    def test_streams_yield_generates_tokens_none_lost_or_doubled(
            self, streamed):
        assert streamed["alive"] == [False] * len(STREAMS)
        assert streamed["outs"] == streamed["want"]
        assert [len(out) for out in streamed["outs"]] \
            == [n for _, n in STREAMS]

    def test_the_wake_up_carries_the_tokens_and_the_net_does_not(
            self, streamed):
        d = streamed["delivery"]
        picked = sum(n for _, n in STREAMS)
        assert d["tokens_picked"] == picked
        # a wait before every poll, ended one way or the other
        assert d["polls"] == d["waits_woken"] + d["waits_timed_out"]
        # a landing is a wake-up, but for a stream's last (its end comes
        # with it); a tenth of room for readers this machine kept from
        # their poll for a whole step, who find two landings behind one
        assert d["waits_woken"] >= picked - len(STREAMS) - picked // 10, d
        # nothing waited out two seconds, and a wake-up finds nothing
        # only at a stream's end or behind a poll that was late itself
        assert d["waits_timed_out"] == 0, d
        assert d["polls_empty"] <= picked // 10, d

    def test_an_engine_that_only_generated_never_waited(self, params):
        eng = _engine(params)
        try:
            reqs = []
            put = eng._queue.put

            def seen(req):
                reqs.append(req)
                put(req)

            eng._queue.put = seen
            outs = [eng.generate([3, 1, 4], 5), eng.generate([1, 5], 9)]
            delivery = eng.stats()["delivery"]
        finally:
            eng.shutdown()
        assert [len(out) for out in outs] == [5, 9]
        assert len(reqs) == 2 and all(r.fresh is None for r in reqs)
        assert {k: v for k, v in delivery.items()
                if k != "pickup_wall_counts"} == dict.fromkeys(
            ("polls", "polls_empty", "tokens_picked", "waits_woken",
             "waits_timed_out"), 0)
        assert sum(delivery["pickup_wall_counts"]) == 0

    @pytest.mark.parametrize("ending", ["cancelled", "failed"])
    def test_a_streams_end_is_a_wake_up_too(self, params, ending):
        """A reader waiting with a time-out of a minute is back at once
        when its request is cancelled or its engine's step fails."""
        eng = _engine(params)
        try:
            eng.generate([9, 9], 2)
            _device_paced(eng, 0.02)            # tokens 20 ms apart
            rid = eng.submit([1, 2, 3], 40)
            req = eng._pending[rid]["req"]
            eng.wait_fresh(rid, 60)             # its first token
            assert eng.poll(rid)["chunks"]

            def end_it():
                if ending == "cancelled":
                    assert eng.cancel(rid)
                else:
                    def broken(*args):
                        raise RuntimeError("no such chip")
                    eng._decode = broken

            # every landing before the end wakes the reader as well: the
            # wait that the end itself ends is the last one
            threading.Timer(0.05, end_it).start()
            t0 = time.monotonic()
            while not (req.done.is_set() or req.cancelled):
                eng.wait_fresh(rid, 60)
                assert time.monotonic() - t0 < 30
            if ending == "cancelled":
                eng.wait_fresh(rid, 60)         # no entry: back at once
                assert time.monotonic() - t0 < 30
                assert eng.poll(rid) == {"chunks": [], "done": True}
                assert req.done.wait(60)
            else:
                with pytest.raises(RuntimeError, match="no such chip"):
                    eng.poll(rid)
            assert eng.stats()["delivery"]["waits_timed_out"] == 0
        finally:
            eng.shutdown()

    def test_a_stream_nothing_wakes_still_ends_by_the_time_out(
            self, params):
        srv = _server(params)
        eng = srv.engine
        try:
            want = eng.generate([2, 7, 1], 12)
            before = eng.stats()["delivery"]
            put = eng._queue.put

            def deaf(req):
                req.fresh.set = lambda: None    # the engine's wake-ups
                put(req)                        # go nowhere

            eng._queue.put = deaf
            out = []
            reader = threading.Thread(target=_read,
                                      args=(srv, out, [2, 7, 1], 12))
            reader.start()
            reader.join(120)
            assert not reader.is_alive()
            after = eng.stats()["delivery"]
        finally:
            eng.shutdown()
        assert out == want
        assert after["waits_woken"] == before["waits_woken"]
        assert after["waits_timed_out"] > before["waits_timed_out"]
        assert after["tokens_picked"] - before["tokens_picked"] == 12


def test_spans_land_in_a_capture_with_their_attributes(params, tmp_path):
    from jax.profiler import ProfileData

    eng = _engine(params)
    try:
        eng.generate([9, 9], 2)                 # compile first
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            eng.generate([5, 17, 99], 4, temperature=0.7)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    host, = [p for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU"]
    seen = {}
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith("rt.engine."):
                seen.setdefault(ev.name[len("rt.engine."):],
                                dict(ev.stats))
    assert PLAIN - {"idle_wait"} <= set(seen), sorted(seen)
    assert seen["prefill"]["prompt_len"] == 3
    assert {"pad_len", "slot", "step"} <= set(seen["prefill"])
    # the wait for the prefill is a span of its own inside it, and both
    # carry the number of the step the prefill runs behind: none is in
    # flight here, so the one the answer's first dispatch then carries
    spans = {ev.name[len("rt.engine."):]: (ev.start_ns,
                                           ev.start_ns + ev.duration_ns)
             for line in host.lines for ev in line.events
             if ev.name in ("rt.engine.prefill", "rt.engine.prefill_fetch")}
    assert spans["prefill"][0] <= spans["prefill_fetch"][0] \
        <= spans["prefill_fetch"][1] <= spans["prefill"][1]
    assert seen["prefill"]["step"] == seen["prefill_fetch"]["step"] \
        == seen["decode_dispatch"]["step"]
    assert "active" in seen["sample"] and "waiting" in seen["admit"]
    # the slots at a temperature that turn: bookkeeping is all that is
    # left inside the span
    assert seen["sample"]["sampled"] == 1
    assert {"step_num", "active"} <= set(seen["turn"])
    # a program and its fetch pair by number, not by place: step N+1 is
    # dispatched before step N is fetched
    assert "step" in seen["decode_dispatch"] and "step" in seen["logits_fetch"]
    order = [(ev.name[len("rt.engine."):], dict(ev.stats)["step"], ev.start_ns)
             for line in host.lines for ev in line.events
             if ev.name in ("rt.engine.decode_dispatch",
                            "rt.engine.logits_fetch")]
    order.sort(key=lambda e: e[2])
    names = [(n, k) for n, k, _ in order]
    first = names[0][1]
    # the answer's three steps: dispatch, dispatch, fetch, dispatch,
    # fetch, fetch
    assert names == [
        ("decode_dispatch", first), ("decode_dispatch", first + 1),
        ("logits_fetch", first), ("decode_dispatch", first + 2),
        ("logits_fetch", first + 1), ("logits_fetch", first + 2)], names


def test_profiling_imports_without_jax():
    code = ("import sys; import ray_tpu.util.profiling as p; "
            "assert 'jax' not in sys.modules, 'jax imported'; "
            "assert callable(p.Phases) and callable(p.part)")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_phases_rows_and_nesting():
    from ray_tpu.util.profiling import Phases

    ph = Phases("t.")
    with ph.step("outer", 3, k=1):
        with ph("inner"):
            time.sleep(0.01)
        with ph("inner"):
            pass
    rows = ph.snapshot()
    assert rows["outer"][0] == 1 and rows["inner"][0] == 2
    assert rows["inner"][1] >= 0.01 and rows["inner"][1] == rows["inner"][2]
    assert rows["outer"][1] >= rows["inner"][1]
    assert rows["outer"][2] == pytest.approx(
        rows["outer"][1] - rows["inner"][1])
    # the first turn's CPU is timed. A sleeping thread uses none: wall
    # minus CPU is the time it was off
    assert rows["inner"][3] == rows["inner"][2] and rows["inner"][4] < 0.005
    # then one turn in CPU_EVERY is; outside a turn every entry is
    for i in range(Phases.CPU_EVERY):
        with ph.step("outer", 4 + i):
            with ph("inner"):
                pass
    timed = ph.snapshot()["inner"]
    assert timed[0] == 2 + Phases.CPU_EVERY
    assert rows["inner"][3] < timed[3] < timed[2]
    with ph("alone"):
        pass
    alone = ph.snapshot()["alone"]
    assert alone[3] == alone[2] > 0.0


class _Clock:
    """``time`` for a Phases whose walls are given."""

    def __init__(self):
        self.now = 0.0

    def monotonic(self):
        return self.now

    def thread_time(self):
        return self.now


def _recorded_walls(seed, n):
    """Turns as a chat cell's look: 12 ms, a few percent of spread, one
    in eight carries a prefill of 20-60 ms more, now and then a stall."""
    rng = np.random.default_rng(seed)
    walls = 0.012 * rng.lognormal(0.0, 0.05, n)
    walls += np.where(rng.random(n) < 0.125, rng.uniform(0.02, 0.06, n), 0.0)
    walls += np.where(rng.random(n) < 0.002, 0.1, 0.0)
    return walls


def _the_readers_percentile():
    """The percentile as the benchmark's reader of ``phase_walls`` takes
    it (``benchmark/layer_metrics/_phase_walls.py``): the one place that
    reads a number from the distribution."""
    folder = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "layer_metrics")
    if folder not in sys.path:
        sys.path.insert(0, folder)      # the readers' shared `_lib`
    spec = importlib.util.spec_from_file_location(
        "_phase_walls", os.path.join(folder, "_phase_walls.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.percentile


@pytest.mark.parametrize("pct", [50, 90, 95, 99])
def test_a_percentile_of_two_snapshots_difference_is_near_the_exact_one(
        pct, monkeypatch):
    from ray_tpu.util.profiling import Phases

    wall_percentile = _the_readers_percentile()
    clock = _Clock()
    monkeypatch.setattr(profiling, "time", clock)
    ph = Phases("t.")

    def enter(walls):
        for wall in walls:
            with ph("turn"):
                clock.now += wall
            clock.now += 1e-4

    earlier = _recorded_walls(1, 500) * 3.0     # before the window
    enter(earlier)
    before = ph.walls()
    window = _recorded_walls(2, 4000)
    enter(window)
    after = ph.walls()
    counts = [b - a for a, b in zip(before["counts"]["turn"],
                                    after["counts"]["turn"])]
    assert sum(counts) == len(window)
    got = wall_percentile(after["edges_s"], counts, pct)
    assert got == pytest.approx(np.percentile(window, pct), rel=0.05)
    # the rows keep their shape: five columns, the readers index them
    row = ph.snapshot()["turn"]
    assert len(row) == 5 and row[0] == 4500
    assert row[1] == pytest.approx(window.sum() + earlier.sum())


def test_walls_outside_the_edges_land_in_the_open_buckets():
    from ray_tpu.util.profiling import (WALL_EDGES_S, count_wall,
                                        wall_counts)

    wall_percentile = _the_readers_percentile()
    edges = WALL_EDGES_S
    assert edges[0] == 16e-6 and 4.0 <= edges[-1] < 4.4
    # at least eight edges a doubling, shared by every distribution
    assert all(b / a <= 2 ** (1 / 8) + 1e-12 for a, b in zip(edges,
                                                             edges[1:]))
    counts = wall_counts()
    for wall in (1e-6, 16e-6, 0.01, 5.0):
        count_wall(counts, wall)
    assert counts[0] == 1 and counts[1] == 1 and counts[-1] == 1
    assert sum(counts) == 4 and len(counts) == len(edges) + 1
    assert wall_percentile(edges, counts, 1) == edges[0]
    assert wall_percentile(edges, counts, 100) == edges[-1]
    assert wall_percentile(edges, wall_counts(), 95) is None


def test_a_phase_exits_wall_costs_under_a_microsecond_or_so():
    """What the always-on distribution adds to a phase's exit: one
    bisect over the shared edges and one increment. Printed; held only
    to ten times the 0.5 us it is meant to stay under (a loaded test
    machine)."""
    import timeit

    from ray_tpu.util.profiling import Phases, count_wall, wall_counts

    counts, n = wall_counts(), 200_000
    empty = min(timeit.repeat(lambda: None, number=n, repeat=3)) / n
    wall = min(timeit.repeat(lambda: count_wall(counts, 0.0123), number=n,
                             repeat=3)) / n - empty
    ph = Phases("t.")

    def entry():
        with ph("x"):
            pass

    whole = min(timeit.repeat(entry, number=n // 4, repeat=3)) / (n // 4)
    print(f"\ncount_wall: {1e6 * wall:.3f} us an exit; a whole phase "
          f"entry and exit outside a capture: {1e6 * whole:.2f} us")
    assert wall < 5e-6


# the engine thread's methods: everything of LLMEngine but what a caller's
# thread runs (submission, polling, stats) and the constructor
_CALLERS = {"__init__", "_builder", "_check_vocab", "generate", "submit",
            "cancel", "submit_prefilled", "poll", "stats", "prefix_digest",
            "shutdown", "_fetch"}


def _opens_a_fetch_phase(node):
    """Whether ``node`` is ``with self._phases("..._fetch", ...):``."""
    return isinstance(node, ast.With) and any(
        isinstance(item.context_expr, ast.Call) and item.context_expr.args
        and isinstance(item.context_expr.args[0], ast.Constant)
        and str(item.context_expr.args[0].value).endswith("_fetch")
        and ast.unparse(item.context_expr.func) in ("self._phases", "phase")
        for item in node.items)


def _reads_outside_a_fetch_phase(tree):
    """[(line, text)] of the calls in ``tree`` that bring a device value
    to the host (``np.asarray(``, ``device_get(``, ``.item()``, the
    engine's own ``_fetch``) and stand under no ``with
    self._phases("..._fetch")``."""
    found = []

    def walk(node, covered):
        covered = covered or _opens_a_fetch_phase(node)
        if isinstance(node, ast.Call):
            text = ast.unparse(node.func)
            if not covered and (
                    text in ("np.asarray", "numpy.asarray", "jax.device_get",
                             "device_get", "self._fetch")
                    or text.endswith((".item", ".block_until_ready"))):
                found.append((node.lineno, text))
        for child in ast.iter_child_nodes(node):
            walk(child, covered)

    walk(tree, False)
    return found


def test_every_blocking_read_of_the_engine_thread_is_in_a_fetch_phase():
    """By the source: no method the engine thread runs brings a
    program's result to the host outside a phase named ``*_fetch``, so
    the phases' rows tell waiting for the device from the host's work."""
    import textwrap

    methods = {name: fn for name, fn in vars(LLMEngine).items()
               if inspect.isfunction(fn) and name not in _CALLERS}
    assert {"_admit", "_admit_block", "_land", "_land_block",
            "_spec_decode_step", "_advance_chunked_prefill",
            "_dispatch"} <= set(methods)
    seen = 0
    for name, fn in methods.items():
        tree = ast.parse(textwrap.dedent(inspect.getsource(fn)))
        assert not _reads_outside_a_fetch_phase(tree), name
        seen += sum(map(_opens_a_fetch_phase, ast.walk(tree)))
    # logits, block, spec, and the prefill's four (admit, block, preload,
    # the last chunk)
    assert seen == 7
    # the walk does find one where there is one
    bare = ast.parse("def f(self):\n    with self._phases('prefill'):\n"
                     "        return np.asarray(self._ids).item()\n")
    assert [t for _, t in _reads_outside_a_fetch_phase(bare)] \
        == ["np.asarray(self._ids).item", "np.asarray"]


def test_a_chunked_prefill_and_a_speculative_turn_wait_in_fetch_phases(
        params):
    """The two other turns that read the device: a chunked prefill's
    last chunk (``prefill_fetch`` inside ``prefill_chunk``) and the
    speculative verify (``spec_fetch`` inside ``spec_verify``)."""
    eng = _engine(params, prefill_chunk=4, kv_block_size=4)
    try:
        assert len(eng.generate(list(range(1, 11)), 3)) == 3
        rows = eng.stats()["phases"]
    finally:
        eng.shutdown()
    assert rows["prefill_chunk"][0] == 3 and rows["prefill_fetch"][0] == 1
    assert rows["prefill_chunk"][1] >= rows["prefill_fetch"][1]
    assert "prefill" not in rows and eng.stats()["admissions"]["prefills"] == 0
    eng = LLMEngine(config=CFG, params=params, kv_cache="slot", num_slots=2,
                    max_seq=64, speculation="ngram", spec_k=3)
    try:
        assert len(eng.generate([9] * 8, 8)) == 8   # an ngram match
        st = eng.stats()
    finally:
        eng.shutdown()
    rows = st["phases"]
    assert st["spec_proposed"] > 0 and rows["spec_fetch"][0] >= 1
    assert rows["spec_fetch"][0] == rows["spec_verify"][0]
    assert rows["spec_verify"][1] >= rows["spec_fetch"][1]


class TestStableNames:
    def test_train_step_program_is_jit_train_step(self):
        from ray_tpu.models.training import (OptimizerConfig,
                                             init_train_state,
                                             make_train_step)
        from ray_tpu.parallel.mesh import MeshConfig, make_mesh
        from ray_tpu.parallel.sharding import FSDP_TP_RULES, set_mesh

        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        rules = FSDP_TP_RULES
        opt = OptimizerConfig(warmup_steps=1, decay_steps=10).make()
        with set_mesh(mesh):
            state, _ = init_train_state(
                lambda key: llama.init_params(CFG, key),
                llama.param_logical_axes(CFG), opt, mesh, rules,
                jax.random.key(0))
            step = make_train_step(
                lambda p, b: llama.loss_fn(p, b, CFG, rules), opt, mesh,
                rules)
            text = step.lower(
                state, {"tokens": jnp.zeros((4, 32), jnp.int32)}).as_text()
        # the serving decode step is jit_step: one name, one program
        assert "module @jit_train_step" in text

    def test_lowered_kernels_carry_their_names(self, params,
                                               monkeypatch):
        from ray_tpu.models.paged_cache import (
            PagedConfig, init_paged_cache, make_paged_decode_step,
            make_paged_prefill)
        from ray_tpu.ops.pallas import flash_attention as fa

        def lower_for_tpu(fn, *args):
            return jax.jit(fn).trace(*args).lower(
                lowering_platforms=("tpu",)).as_text()

        S = jax.ShapeDtypeStruct
        q, kv = (S((1, 4, 256, 128), jnp.bfloat16),
                 S((1, 2, 256, 128), jnp.bfloat16))
        vec = S((1, 4, 256), jnp.float32)
        kw = dict(causal=True, scale=0.1, block_q=128, block_kv=128)
        fwd = lower_for_tpu(functools.partial(
            fa.flash_attention_fwd_pallas, **kw), q, kv, kv)
        assert 'kernel_name = "flash_attention_fwd"' in fwd
        bwd = lower_for_tpu(functools.partial(
            fa.flash_attention_bwd_pallas, **kw), q, kv, kv, vec, vec, q)
        assert 'kernel_name = "flash_attention_dq"' in bwd
        assert 'kernel_name = "flash_attention_dkv"' in bwd

        # the decode step asks jax.default_backend() at trace time
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        page = PagedConfig(num_blocks=9, block_size=16, max_seq=64)
        cache = init_paged_cache(CFG, page, 2)
        step = make_paged_decode_step(params, CFG, page)
        text = step.jitted.trace(
            params, cache, jnp.zeros((2, page.max_blocks_per_seq),
                                     jnp.int32),
            jnp.zeros(2, jnp.int32), jnp.ones(2, bool)).lower(
                lowering_platforms=("tpu",)).as_text()
        # decode_step_dev_ms.* reads ^jit_step and prefill_dev_share_pct
        # ^jit_prefill: the two programs keep these names
        assert "module @jit_step" in text
        assert 'kernel_name = "paged_decode_attention"' in text
        prefill = make_paged_prefill(params, CFG, page)
        assert prefill.jitted.__name__ == "prefill"
