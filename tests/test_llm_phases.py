"""The engine loop's own clock: named phases (``stats()["phases"]`` and
spans in a profiler capture), per-request lifecycle records
(``stats()["requests"]``), and the stable names of the device side
(``jit_train_step``, the Pallas kernels). CPU, debug model."""

import functools
import glob
import subprocess
import sys
import threading
import time

import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.serve.llm import LLMEngine

CFG = llama.CONFIGS["debug"]
# the phases of the table in PERF.md section 3 that the plain path reaches
PLAIN = {"turn", "grow", "admit", "prefill", "decode_dispatch",
         "logits_fetch", "sample", "idle_wait"}
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
            "blocking": blocking}


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
    assert {"pad_len", "slot"} <= set(seen["prefill"])
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
            "assert callable(p.Phases) and callable(p.annotate)")
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
