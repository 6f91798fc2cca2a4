"""LLM decode-path + continuous-batching engine tests (CPU mesh)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import llama

CFG = llama.CONFIGS["debug"]


def _greedy_reference(params, prompt, n_tokens):
    """Oracle: iterative full-forward greedy decode."""
    toks = list(prompt)
    for _ in range(n_tokens):
        logits = llama.forward(params, jnp.asarray([toks]), CFG)
        toks.append(int(np.asarray(logits)[0, -1].argmax()))
    return toks[len(prompt):]


class TestDecodePath:
    def test_decode_matches_full_forward(self):
        from ray_tpu.models.decoding import (
            init_cache, make_decode_step, make_prefill)

        params = llama.init_params(CFG, jax.random.key(0))
        prompt = [5, 17, 99, 3, 42]
        n_new = 8
        want = _greedy_reference(params, prompt, n_new)

        cache = init_cache(CFG, num_slots=2, max_seq=64)
        prefill = make_prefill(params, CFG)
        decode = make_decode_step(params, CFG)
        tokens = np.zeros((1, 32), np.int32)
        tokens[0, :len(prompt)] = prompt
        cache, logits = prefill(cache, jnp.asarray(tokens), len(prompt), 0)
        got = [int(np.asarray(logits).argmax())]
        last = np.array([got[0], 0], np.int32)
        active = np.array([True, False])
        for _ in range(n_new - 1):
            cache, logits = decode(cache, jnp.asarray(last),
                                   jnp.asarray(active))
            tok = int(np.asarray(logits)[0].argmax())
            got.append(tok)
            last[0] = tok
        assert got == want

    def test_inactive_slots_untouched(self):
        from ray_tpu.models.decoding import init_cache, make_decode_step

        params = llama.init_params(CFG, jax.random.key(0))
        cache = init_cache(CFG, num_slots=2, max_seq=64)
        decode = make_decode_step(params, CFG)
        cache, _ = decode(cache, jnp.asarray(np.array([1, 2], np.int32)),
                          jnp.asarray(np.array([True, False])))
        assert int(cache["length"][0]) == 1
        assert int(cache["length"][1]) == 0


class TestEngine:
    def test_concurrent_generations_match_sequential(self):
        from ray_tpu.serve.llm import LLMEngine

        params = llama.init_params(CFG, jax.random.key(0))
        engine = LLMEngine(config=CFG, params=params, num_slots=4,
                           max_seq=64)
        prompts = [[5, 17, 99], [7, 7], [1, 2, 3, 4, 5, 6], [100]]
        import concurrent.futures as cf

        with cf.ThreadPoolExecutor(4) as pool:
            futs = [pool.submit(engine.generate, p, 6) for p in prompts]
            results = [f.result(timeout=120) for f in futs]
        engine.shutdown()
        for p, r in zip(prompts, results):
            assert r == _greedy_reference(params, p, 6), (p, r)

    def test_eos_and_max_tokens(self):
        from ray_tpu.serve.llm import LLMEngine

        params = llama.init_params(CFG, jax.random.key(0))
        engine = LLMEngine(config=CFG, params=params, num_slots=2,
                           max_seq=64)
        out = engine.generate([5, 17, 99], max_tokens=4)
        assert len(out) == 4
        # eos: use the first generated token as eos → stops at 1
        ref = _greedy_reference(params, [5, 17, 99], 1)
        out2 = engine.generate([5, 17, 99], max_tokens=10,
                               eos_token=ref[0])
        assert out2 == ref
        stats = engine.stats()
        assert stats["tokens_generated"] >= 3
        engine.shutdown()

    def test_validation(self):
        from ray_tpu.serve.llm import LLMEngine

        engine = LLMEngine(config=CFG, num_slots=2, max_seq=64)
        with pytest.raises(ValueError):
            engine.generate([], 4)
        with pytest.raises(ValueError):
            engine.generate([1] * 60, 10)
        engine.shutdown()


class TestChunkedPrefill:
    """vLLM-class chunked prefill (opt-in prefill_chunk, slot cache):
    long prompts prefill one chunk per engine iteration, interleaved
    with decode of other slots; outputs must match the non-chunked
    engine exactly (greedy + same params)."""

    def test_outputs_match_unchunked(self):
        import jax

        from ray_tpu.models import llama
        from ray_tpu.serve.llm import LLMEngine

        cfg = llama.CONFIGS["debug"]
        params = llama.init_params(cfg, jax.random.key(0))
        prompts = [
            list(range(1, 60)),          # long: chunks of 16
            [5, 6, 7],                   # short: direct prefill
            list(range(20, 55)),         # long again
        ]
        base = LLMEngine(config=cfg, params=params, num_slots=4,
                         kv_cache="slot", seed=0)
        want = [base.generate(p, max_tokens=8) for p in prompts]
        base.shutdown()

        eng = LLMEngine(config=cfg, params=params, num_slots=4,
                        kv_cache="slot", seed=0, prefill_chunk=16)
        try:
            got = [eng.generate(p, max_tokens=8) for p in prompts]
            assert got == want
            st = eng.stats()
            # 59 tokens -> 4 chunks; 35 tokens -> 3; short prompt -> 0
            assert st["prefill_chunks_run"] == 7, st
            assert st["prefilling_slots"] == 0
        finally:
            eng.shutdown()

    def test_concurrent_long_and_short(self):
        import jax

        from ray_tpu.models import llama
        from ray_tpu.serve.llm import LLMEngine

        cfg = llama.CONFIGS["debug"]
        params = llama.init_params(cfg, jax.random.key(0))
        eng = LLMEngine(config=cfg, params=params, num_slots=4,
                        kv_cache="slot", seed=0, prefill_chunk=8)
        base = LLMEngine(config=cfg, params=params, num_slots=4,
                         kv_cache="slot", seed=0)
        try:
            long_id = eng.submit(list(range(2, 50)), max_tokens=6)
            short_id = eng.submit([9, 8, 7], max_tokens=6)
            import time as _t

            deadline = _t.monotonic() + 120
            acc = {long_id: [], short_id: []}
            done = set()
            while _t.monotonic() < deadline and len(done) < 2:
                for rid in (long_id, short_id):
                    if rid in done:
                        continue
                    r = eng.poll(rid)
                    acc[rid].extend(r["chunks"])
                    if r["done"]:
                        done.add(rid)
                _t.sleep(0.01)
            assert len(done) == 2
            assert acc[long_id] == base.generate(
                list(range(2, 50)), max_tokens=6)
            assert acc[short_id] == base.generate([9, 8, 7], max_tokens=6)
        finally:
            eng.shutdown()
            base.shutdown()

    def test_paged_chunk_must_align_to_blocks(self):
        from ray_tpu.serve.llm import LLMEngine

        with pytest.raises(ValueError, match="multiple of"):
            LLMEngine(model="debug", kv_cache="paged", kv_block_size=16,
                      prefill_chunk=24)

    def test_paged_outputs_match_unchunked(self):
        import jax

        from ray_tpu.models import llama
        from ray_tpu.serve.llm import LLMEngine

        cfg = llama.CONFIGS["debug"]
        params = llama.init_params(cfg, jax.random.key(0))
        prompts = [list(range(1, 60)), [5, 6, 7], list(range(20, 55))]
        base = LLMEngine(config=cfg, params=params, num_slots=4,
                         kv_cache="paged", kv_block_size=16, seed=0)
        want = [base.generate(p, max_tokens=8) for p in prompts]
        base.shutdown()

        eng = LLMEngine(config=cfg, params=params, num_slots=4,
                        kv_cache="paged", kv_block_size=16, seed=0,
                        prefill_chunk=16)
        try:
            got = [eng.generate(p, max_tokens=8) for p in prompts]
            assert got == want
            assert eng.stats()["prefill_chunks_run"] == 7
        finally:
            eng.shutdown()


class TestSpeculativeDecoding:
    """Prompt-lookup (ngram) speculative decoding: acceptance only skips
    compute — greedy outputs must be IDENTICAL to the plain engine, with
    or without proposal hits."""

    def _outputs(self, prompts, **kw):
        import jax

        from ray_tpu.models import llama
        from ray_tpu.serve.llm import LLMEngine

        cfg = llama.CONFIGS["debug"]
        params = llama.init_params(cfg, jax.random.key(0))
        eng = LLMEngine(config=cfg, params=params, num_slots=4,
                        kv_cache="slot", seed=0, **kw)
        try:
            outs = [eng.generate(p, max_tokens=12) for p in prompts]
            return outs, eng.stats()
        finally:
            eng.shutdown()

    def test_greedy_parity_with_and_without_proposals(self):
        prompts = [
            # repetitive: the trailing 2-gram recurs, proposals fire
            [3, 4, 5, 6, 3, 4, 5, 6, 3, 4],
            # structureless: lookup misses, pure fallback
            [11, 23, 7, 91, 2, 57],
        ]
        want, _ = self._outputs(prompts)
        got, st = self._outputs(prompts, speculation="ngram", spec_k=4)
        assert got == want
        assert st["spec_proposed"] > 0  # machinery engaged on prompt 1

    def test_rejected_speculation_state_stays_consistent(self):
        """Even with 0 acceptances (random-weight model rarely agrees
        with lookup), continued generation after speculative steps must
        stay exact — the rejected rows past the length are invisible."""
        prompt = [9, 9, 9, 9, 9, 9, 9, 9]  # guaranteed ngram match
        want, _ = self._outputs([prompt])
        got, st = self._outputs([prompt], speculation="ngram", spec_k=3)
        assert got == want
        assert st["spec_proposed"] >= 1

    def test_validation(self):
        import pytest as _pytest

        from ray_tpu.serve.llm import LLMEngine

        # draft is a real method now, but needs a draft model source
        with _pytest.raises(ValueError, match="draft_model"):
            LLMEngine(model="debug", kv_cache="slot", speculation="draft")
        with _pytest.raises(ValueError, match="one of"):
            LLMEngine(model="debug", kv_cache="slot", speculation="medusa")
        with _pytest.raises(ValueError, match="slot"):
            LLMEngine(model="debug", kv_cache="paged", speculation="ngram")


# ------------------------------------------------ one step ahead of the host
def host_fed(cfg, params, requests, *, num_slots, max_seq, block, seed=0):
    """What the engine has to answer, token for token: each of
    ``requests`` = ``(prompt, max_tokens, temperature, eos_token)``,
    numbered in this order, alone in slot 0 of the model's own paged
    programs, every token fetched and fed back BY THE HOST before the
    next step is built. Returns the answers and the model's counters
    summed over the decode steps."""
    from ray_tpu.models.paged_cache import pad_to_block_bucket
    from ray_tpu.models.serving import serving_model
    from ray_tpu.serve.llm import sample_ids

    prog = serving_model(cfg).paged(
        params, num_slots=num_slots, max_seq=max_seq, block_size=block,
        pool_tokens=num_slots * max_seq)
    cache, alloc = prog.cache, prog.alloc
    key, draw = jax.random.key(seed), jax.jit(sample_ids)

    def pick(row, temperature, number, position):
        if temperature <= 0.0:
            return int(np.asarray(row).argmax())
        return int(draw(row[None], np.array([temperature], np.float32), key,
                        np.array([number], np.int32),
                        np.array([position], np.int32))[0])

    active = np.arange(num_slots) == 0
    answers, counters = [], np.zeros(len(prog.counters))
    for number, (prompt, n, temperature, eos) in enumerate(requests):
        plen = len(prompt)
        assert alloc.ensure(0, plen + 1)
        tokens = np.zeros((1, pad_to_block_bucket(plen, block)), np.int32)
        tokens[0, :plen] = prompt
        cache, logits = prog.prefill(cache, alloc.table_rows(0),
                                     jnp.asarray(tokens), plen, 0)
        out = [pick(logits, temperature, number, plen)]
        while len(out) < n and out[-1] != eos:
            cached = plen + len(out) - 1
            if hasattr(alloc, "trim"):
                alloc.trim(0, cached + 1)
            assert alloc.ensure(0, cached + 1)
            last = np.zeros(num_slots, np.int32)
            last[0] = out[-1]
            cache, logits = prog.decode(cache, alloc.device_tables(),
                                        jnp.asarray(last),
                                        jnp.asarray(active))
            if prog.counters:
                counters += np.asarray(cache["counters"])
            out.append(pick(logits[0], temperature, number, cached + 1))
        alloc.release(0)
        answers.append(out)
    return answers, counters


def _drain_polls(eng, rids, timeout_s=300.0):
    import time

    got = {rid: [] for rid in rids}
    left, deadline = set(rids), time.monotonic() + timeout_s
    while left:
        assert time.monotonic() < deadline, "the engine did not answer"
        for rid in list(left):
            st = eng.poll(rid)
            got[rid].extend(st["chunks"])
            if st["done"]:
                left.discard(rid)
        time.sleep(0.002)
    return [got[rid] for rid in rids]


def _wait_for_tokens(eng, rid, n, timeout_s=120.0):
    """Until the request behind ``rid`` has ``n`` tokens; its record."""
    import time

    req = eng._pending[rid]["req"]
    deadline = time.monotonic() + timeout_s
    while len(req.output) < n and not req.done.is_set():
        assert time.monotonic() < deadline
        time.sleep(0.001)
    return req


ENGINE = dict(num_slots=4, max_seq=64, kv_block_size=16)
# (prompt, max_tokens, temperature, eos_token), in the order submitted
SCENARIOS = {
    "greedy": [([5, 17, 99], 9, 0.0, None), ([7, 7], 12, 0.0, None),
               ([1, 2, 3, 4, 5, 6], 7, 0.0, None)],
    "temperature": [([5, 17, 99], 9, 0.8, None), ([7, 7], 12, 0.8, None),
                    ([5, 17, 99], 10, 1.3, None)],
    "mixed_turns": [([5, 17, 99], 9, 0.0, None), ([7, 7], 12, 0.9, None),
                    ([100], 10, 0.0, None), ([3, 1, 4, 1, 5], 6, 0.7, None)],
    # more requests than slots: a slot gets its next occupant while the
    # step after its last occupant's end is in flight
    "slot_reuse": [([5 + i, 17, 99][:1 + i % 3], 4 + 3 * (i % 4),
                    0.6 * (i % 2), None) for i in range(9)],
}


class TestOneStepAhead:
    """The plain decode turn dispatches step N+1 from the ids step N left
    on the device and reads step N under it. None of that may show in an
    answer."""

    @pytest.fixture(scope="class")
    def params(self):
        return llama.init_params(CFG, jax.random.key(0))

    def _engine(self, params, **kw):
        from ray_tpu.serve.llm import LLMEngine

        return LLMEngine(config=CFG, params=params, seed=3,
                         **dict(ENGINE, **kw))

    def _reference(self, params, requests, **kw):
        e = dict(ENGINE, **kw)
        return host_fed(CFG, params, requests, num_slots=e["num_slots"],
                        max_seq=e["max_seq"], block=e["kv_block_size"],
                        seed=3)[0]

    @pytest.mark.parametrize("name", [*SCENARIOS, "admitted_midstream",
                                      "one_cancelled"])
    def test_answers_are_those_of_the_host_fed_steps(self, params, name):
        slots = 2 if name == "slot_reuse" else 4
        requests = SCENARIOS.get(name) or [
            ([5, 17, 99], 16, 0.0, None), ([7, 7], 14, 0.8, None),
            ([1, 2, 3, 4, 5, 6], 9, 0.0, None), ([100, 3], 8, 0.7, None)]
        want = self._reference(params, requests, num_slots=slots)
        eng = self._engine(params, num_slots=slots)
        try:
            def submit(r):
                return eng.submit(r[0], r[1], temperature=r[2],
                                  eos_token=r[3])

            cancelled = None
            if name == "admitted_midstream":
                rids = [submit(r) for r in requests[:2]]
                _wait_for_tokens(eng, rids[0], 4)
                rids += [submit(r) for r in requests[2:]]
            else:
                rids = [submit(r) for r in requests]
            if name == "one_cancelled":
                cancelled = _wait_for_tokens(eng, rids[1], 3)
                assert eng.cancel(rids[1])
                assert cancelled.done.wait(60)
            got = _drain_polls(eng, [r for r in rids
                                     if cancelled is None or r != rids[1]])
            st = eng.stats()
        finally:
            eng.shutdown()
        if cancelled is not None:
            cut = cancelled.output
            assert 3 <= len(cut) < 14 and cut == want[1][:len(cut)]
            del want[1]
        assert got == want
        assert st["kv_blocks_free"] == st["kv_blocks_total"]
        assert st["turns"]["overlapped"] >= 3
        # no request names an eos_token: every end was foreseen, but
        # a cancel can come between a dispatch and its fetch
        assert st["turns"]["surplus_dropped"] <= (cancelled is not None)

    def test_an_eos_ends_the_answer_and_costs_one_dropped_id(self, params):
        import time

        ref = self._reference(params, [([5, 17, 99], 12, 0.0, None)])[0]
        k = next(i for i in range(1, 12) if ref[i] not in ref[:i])
        eng = self._engine(params)
        try:
            out = eng.generate([5, 17, 99], max_tokens=12, eos_token=ref[k])
            deadline = time.monotonic() + 60
            while (st := eng.stats())["steps"] <= k:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            # the step after the EOS ran: its id is dropped, its row lay
            # in a block the slot still owned, and the blocks are back
            assert out == ref[:k + 1]
            assert st["turns"]["surplus_dropped"] == 1
            assert st["steps"] == k + 1
            assert st["kv_blocks_free"] == st["kv_blocks_total"]
            assert st["active_slots"] == 0
            eng._alloc.check_invariants()
            # the next occupant of that slot is answered as if alone
            nxt = ([9, 8, 7, 6], 10, 0.0, None)
            assert eng.generate(*nxt[:2]) == self._reference(
                params, [nxt])[0]
            assert eng.stats()["turns"]["surplus_dropped"] == 1
        finally:
            eng.shutdown()

    def test_an_end_by_max_tokens_costs_no_step(self, params):
        eng = self._engine(params)
        try:
            assert len(eng.generate([5, 17, 99], max_tokens=6)) == 6
            # up to max_seq: the other end the host foresees
            assert len(eng.generate([1] * 60, max_tokens=4)) == 4
            st = eng.stats()
        finally:
            eng.shutdown()
        assert st["steps"] == 5 + 3 and st["tokens_generated"] == 5 + 3
        assert st["turns"] == {"overlapped": 4 + 2, "drained": 2,
                               "surplus_dropped": 0}

    def test_a_cancel_mid_prefill_is_the_prefills_to_see(self, params):
        """A slot whose prompt is still going in chunk by chunk runs in
        no step, so no bookkeeping meets it: its last chunk ends it."""
        import time

        running = ([5, 17, 99], 40, 0.0, None)
        want = self._reference(params, [running], max_seq=512)[0]
        eng = self._engine(params, max_seq=512, prefill_chunk=16)
        try:
            rid = eng.submit(*running[:2])
            long = eng.submit([1 + i % 200 for i in range(400)], 8)
            req = eng._pending[long]["req"]
            deadline = time.monotonic() + 60
            while not eng.stats()["prefilling_slots"]:
                assert time.monotonic() < deadline
                time.sleep(0.0005)
            assert eng.cancel(long)
            assert req.done.wait(60)
            assert _drain_polls(eng, [rid]) == [want]
            st = eng.stats()
        finally:
            eng.shutdown()
        assert req.error is None and req.record[-1] == "cancelled"
        assert st["prefilling_slots"] == 0 and st["active_slots"] == 0
        assert st["kv_blocks_free"] == st["kv_blocks_total"]

    def test_a_preemption_waits_for_the_step_in_flight(self, params):
        """test_llm_phases.py's tight pool: the victim resumes from
        prompt + output, so its last token has to be on the host."""
        import threading

        requests = [([2 + i, 9, 4], 40, 0.0, None) for i in range(3)]
        kw = dict(num_slots=3, max_seq=256)
        want = self._reference(params, requests, **kw)
        eng = self._engine(params, kv_pool_tokens=96, **kw)
        outs = {}
        try:
            threads = [threading.Thread(
                target=lambda i=i, r=r: outs.__setitem__(
                    i, eng.generate(r[0], r[1], timeout_s=180)))
                for i, r in enumerate(requests)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            st = eng.stats()
        finally:
            eng.shutdown()
        assert st["preemptions"] >= 1
        assert [outs[i] for i in range(3)] == want
        # every step after a preemption starts from the host's tokens
        assert st["turns"]["drained"] >= 1 + st["preemptions"]
        assert st["turns"]["surplus_dropped"] == 0
        assert st["kv_blocks_free"] == st["kv_blocks_total"]

    @pytest.mark.parametrize("speculation", [None, "ngram"])
    def test_turns_count_every_step_once(self, params, speculation):
        kw = dict(kv_cache="slot", speculation=speculation, spec_k=3) \
            if speculation else {}
        eng = self._engine(params, **kw)
        try:
            eng.generate([9, 9, 9, 9, 9, 9, 9, 9], max_tokens=12)
            eng.generate([11, 23, 7, 91, 2, 57], max_tokens=9)
        finally:
            eng.shutdown()
        st = eng.stats()
        turns = st["turns"]
        assert turns["overlapped"] + turns["drained"] == st["steps"]
        assert turns["surplus_dropped"] == 0
        if speculation:
            # a proposer reads the host's tokens: nothing runs ahead
            assert turns["overlapped"] == 0 and st["spec_proposed"] > 0
        else:
            assert turns == {"overlapped": 10 + 7, "drained": 2,
                             "surplus_dropped": 0}
