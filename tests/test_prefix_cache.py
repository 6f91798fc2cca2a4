"""Radix prefix KV cache subsystem (CPU mesh).

Correctness bars, per the subsystem's contract:

* greedy outputs are BIT-IDENTICAL cache-on vs cache-off, including the
  6-requests-on-3-slots churn shape from test_speculation;
* eviction can never reclaim a block whose refcount > 0 — i.e. a block
  any live slot's table still references (``BlockAllocator.
  check_invariants`` is the oracle, run after every chaos scenario);
* an injected fault at either prefix fault point degrades to a COLD
  prefill with a typed counter bump — never a wrong token, never a hang.
"""

import threading

import numpy as np
import pytest

import jax

from ray_tpu.models import llama
from ray_tpu.models.paged_cache import BlockAllocator, PagedConfig
from ray_tpu.models.prefix_cache import RadixPrefixCache

CFG = llama.CONFIGS["debug"]
PARAMS = llama.init_params(CFG, jax.random.key(0))

# 24-token shared "system prompt" (3 blocks at kv_block_size=8) + tails
SYSTEM = list(range(1, 25))
TAILS = [
    [30, 31, 32, 33],
    [40, 41],
    [50, 51, 52, 53, 54, 55],
    [60],
    [70, 71, 72],
    [80, 81, 82, 83, 84],
]
PROMPTS = [SYSTEM + t for t in TAILS]


def _engine(**kw):
    from ray_tpu.serve.llm import LLMEngine

    kw.setdefault("num_slots", 3)
    kw.setdefault("max_seq", 64)
    kw.setdefault("kv_block_size", 8)
    return LLMEngine(config=CFG, params=PARAMS, seed=0, **kw)


def _baseline(prompts, lens):
    eng = _engine(prefix_cache="off")
    try:
        return [eng.generate(p, max_tokens=n)
                for p, n in zip(prompts, lens)]
    finally:
        eng.shutdown()


def _alloc(num_blocks=12, block_size=4, num_slots=3, max_seq=32):
    page = PagedConfig(num_blocks=num_blocks, block_size=block_size,
                       max_seq=max_seq)
    return BlockAllocator(page, num_slots)


class TestAllocatorRefcounts:
    def test_adopt_aliases_and_release_keeps_shared(self):
        al = _alloc()
        assert al.ensure(0, 8)                      # 2 private blocks
        shared = list(al._owned[0])
        al.ref_blocks(shared)                       # tree takes a ref
        assert [al.refcount(b) for b in shared] == [2, 2]
        al.adopt(1, shared)                         # second slot aliases
        assert [al.refcount(b) for b in shared] == [3, 3]
        assert al.tables[1, 0] == shared[0] and al.tables[1, 1] == shared[1]
        free_before = al.free_blocks()
        al.release(0)
        al.release(1)
        # tree still holds them: nothing returned to the pool
        assert al.free_blocks() == free_before
        assert [al.refcount(b) for b in shared] == [1, 1]
        al.check_invariants()
        assert al.unref_blocks(shared) == shared    # last ref frees
        assert al.free_blocks() == free_before + 2
        al.check_invariants()

    def test_cow_swaps_private_block(self):
        al = _alloc()
        assert al.ensure(0, 8)
        shared = list(al._owned[0])
        al.ref_blocks(shared)
        al.adopt(1, shared)
        src, dst = al.cow(1, 1)                     # diverge at block 1
        assert src == shared[1] and dst not in shared
        assert al.refcount(src) == 2                # slot 0 + tree
        assert al.refcount(dst) == 1                # slot 1 private
        assert al.tables[1, 1] == dst
        al.check_invariants()
        al.release(1)
        assert al.refcount(dst) == 0                # private copy freed
        assert al.refcount(src) == 2                # shared untouched
        al.check_invariants()

    def test_cow_refused_when_pool_empty(self):
        al = _alloc(num_blocks=3, block_size=4, num_slots=2)
        assert al.ensure(0, 8)                      # both usable blocks
        al.adopt(1, [al._owned[0][0]])
        assert al.cow(1, 0) is None                 # no free block: no COW

    def test_release_order_independence(self):
        al = _alloc()
        assert al.ensure(0, 8)
        shared = list(al._owned[0])
        al.adopt(1, shared)
        al.adopt(2, shared)
        al.release(0)                               # original owner first
        al.check_invariants()
        assert all(al.refcount(b) == 2 for b in shared)
        al.release(2)
        al.release(1)
        al.check_invariants()
        assert all(al.refcount(b) == 0 for b in shared)


class TestRadixTree:
    def _tree(self, al, budget_blocks=64):
        return RadixPrefixCache(al, bytes_per_block=1,
                                budget_bytes=budget_blocks)

    def test_match_insert_roundtrip(self):
        al = _alloc()
        tree = self._tree(al)
        toks = list(range(16))                      # 4 blocks of 4
        assert al.ensure(0, 16)
        blocks = list(al._owned[0])
        assert tree.insert(toks, blocks) == 4
        al.release(0)
        m = tree.match(toks)
        assert m.blocks == blocks and m.matched == 16 and m.cow is None
        # proper prefix of the cached path
        m = tree.match(toks[:8])
        assert m.blocks == blocks[:2] and m.matched == 8
        tree._alloc.check_invariants()

    def test_match_reports_midblock_cow(self):
        al = _alloc()
        tree = self._tree(al)
        toks = list(range(16))
        assert al.ensure(0, 16)
        blocks = list(al._owned[0])
        tree.insert(toks, blocks)
        al.release(0)
        # agrees through token 5, diverges inside block 1
        m = tree.match([0, 1, 2, 3, 4, 5, 99, 98])
        assert m.blocks == blocks[:1]
        assert m.cow == (blocks[1], 2)
        assert m.matched == 6
        assert tree.cow_hits == 1

    def test_eviction_skips_referenced_blocks(self):
        al = _alloc()
        tree = self._tree(al)
        toks = list(range(8))
        assert al.ensure(0, 8)
        blocks = list(al._owned[0])
        tree.insert(toks, blocks)
        # slot 0 still references both blocks: nothing is evictable
        assert tree.evict_for(2) == 0
        assert al.refcount(blocks[0]) == 2
        al.check_invariants()
        al.release(0)
        # tree-only references now: leaf-first LRU eviction reclaims
        assert tree.evict_for(2) == 2
        assert al.refcount(blocks[0]) == 0
        assert tree.cached_blocks == 0
        al.check_invariants()

    def test_byte_budget_evicts_lru(self):
        al = _alloc(num_blocks=16, num_slots=2)
        tree = self._tree(al, budget_blocks=2)
        assert al.ensure(0, 8)
        a = list(al._owned[0])
        tree.insert([1, 2, 3, 4, 5, 6, 7, 8], a)
        al.release(0)
        assert tree.cached_blocks == 2
        assert al.ensure(1, 8)
        b = list(al._owned[1])
        tree.insert([9, 10, 11, 12, 13, 14, 15, 16], b)
        al.release(1)
        # budget 2: the older path was evicted to admit the newer one
        assert tree.cached_blocks == 2
        assert tree.evicted_blocks >= 2
        assert tree.match([1, 2, 3, 4]).matched == 0
        al.check_invariants()

    def test_budget_insert_never_evicts_own_path(self):
        """_make_room during an insert must not reclaim the node the
        walk is standing on (regression: the rest of the path would
        graft onto a detached subtree)."""
        al = _alloc(num_blocks=16, num_slots=2)
        tree = self._tree(al, budget_blocks=2)
        assert al.ensure(0, 8)
        tree.insert([1, 2, 3, 4, 5, 6, 7, 8], list(al._owned[0]))
        al.release(0)
        # same first block, new second block: the walk reuses node 1,
        # then needs room for node 2 — with budget 2 the only evictable
        # leaf was node 2 of the old path
        assert al.ensure(1, 8)
        tree.insert([1, 2, 3, 4, 50, 51, 52, 53], list(al._owned[1]))
        al.release(1)
        assert tree.match([1, 2, 3, 4, 50, 51, 52, 53]).matched == 8
        # reachable node count agrees with the accounting
        n = 0
        stack = list(tree._root.children.values())
        while stack:
            node = stack.pop()
            n += 1
            stack.extend(node.children.values())
        assert n == tree.cached_blocks
        al.check_invariants()

    def test_insert_dedups_existing_path(self):
        al = _alloc()
        tree = self._tree(al)
        toks = [1, 2, 3, 4, 5, 6, 7, 8]
        assert al.ensure(0, 8)
        tree.insert(toks, list(al._owned[0]))
        # a second slot computed the same prefix in different physical
        # blocks: nothing new is cached, the second copy stays private
        assert al.ensure(1, 8)
        assert tree.insert(toks, list(al._owned[1])) == 0
        assert tree.cached_blocks == 2
        al.release(0)
        al.release(1)
        al.check_invariants()

    def test_tenant_accounting_and_cap(self):
        al = _alloc(num_blocks=32, num_slots=2, max_seq=64)
        tree = self._tree(al, budget_blocks=16)
        assert al.ensure(0, 16)
        assert tree.insert(list(range(16)), list(al._owned[0]),
                           tenant="a", max_new=2) == 2
        assert tree.tenant_blocks == {"a": 2}
        al.release(0)
        tree.evict_for(2)
        assert tree.tenant_blocks == {}

    def test_digest_matches_router_hashes(self):
        """The tree's advertisement hashes the SAME bytes the handle
        router hashes for a token-list routing key."""
        from ray_tpu.serve.handle import _RouterState

        al = _alloc(num_blocks=34, block_size=16, num_slots=1,
                    max_seq=128)
        tree = RadixPrefixCache(al, bytes_per_block=1, budget_bytes=64)
        toks = list(range(48))                      # 3 blocks of 16
        assert al.ensure(0, 48)
        tree.insert(toks, list(al._owned[0]))
        dig = set(tree.digest())
        want = _RouterState._prefix_hashes(toks)    # cuts 48, 32, 16
        assert set(want) <= dig


class TestEngineParity:
    def test_shared_prefix_hits_and_greedy_parity(self):
        lens = [10] * 4
        want = _baseline(PROMPTS[:4], lens)
        eng = _engine(prefix_cache="radix")
        try:
            got = [eng.generate(p, max_tokens=n)
                   for p, n in zip(PROMPTS[:4], lens)]
            st = eng.stats()
            eng._alloc.check_invariants()
        finally:
            eng.shutdown()
        assert got == want
        assert st["prefix_hits"] >= 3               # every repeat hits
        assert st["prefix_cache"]["hit_tokens"] >= 3 * (len(SYSTEM) // 8) * 8

    def test_six_requests_three_slots_parity(self):
        """The test_speculation churn shape: 6 staggered requests on 3
        slots, admission/finish/cache-insert racing while other slots
        decode — radix on must equal cache-off token-for-token."""
        lens = [14, 6, 10, 8, 12, 5]
        want = dict(enumerate(_baseline(PROMPTS, lens)))

        eng = _engine(prefix_cache="radix")
        got, errs = {}, []

        def client(i):
            try:
                got[i] = eng.generate(PROMPTS[i], max_tokens=lens[i],
                                      timeout_s=240)
            except Exception as e:  # noqa: BLE001
                errs.append((i, e))

        try:
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(PROMPTS))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240)
            st = eng.stats()
            eng._alloc.check_invariants()
        finally:
            eng.shutdown()
        assert not errs, errs
        assert got == want
        assert st["prefix_hits"] >= 1

    def test_cow_midblock_divergence_parity(self):
        """Second prompt diverges INSIDE a cached block: the engine must
        device-copy the divergence block and resume prefill mid-block,
        with the cached original serving the first prompt unchanged."""
        a = SYSTEM + [30, 31, 32, 33, 34, 35, 36, 37]   # 32 = 4 blocks
        b = a[:27] + [99, 98, 97, 96, 95]               # diverges at 27
        want = _baseline([a, b, a], [8, 8, 8])
        eng = _engine(prefix_cache="radix")
        try:
            got = [eng.generate(p, max_tokens=8) for p in (a, b, a)]
            st = eng.stats()
            eng._alloc.check_invariants()
        finally:
            eng.shutdown()
        assert got == want
        assert st["prefix_cache"]["cow_hits"] >= 1


class TestChaos:
    def test_pool_pressure_preemption_and_abort(self):
        """Tiny pool: admission evicts tree blocks under pressure,
        decode growth preempts slots whose blocks the tree still shares,
        and two requests are aborted mid-flight. The allocator invariant
        check is the oracle that eviction never reclaimed a referenced
        block; afterwards clear() must return every tree block."""
        import time

        # 12 usable blocks of 8 for 3 slots of ~5-block requests
        eng = _engine(prefix_cache="radix", kv_pool_tokens=96)
        try:
            rids = [eng.submit(p, max_tokens=12) for p in PROMPTS]
            eng.cancel(rids[2])
            eng.cancel(rids[4])
            deadline = time.monotonic() + 240
            pending = set(rids)
            while pending:
                assert time.monotonic() < deadline, "chaos leg hung"
                for rid in list(pending):
                    if eng.poll(rid)["done"]:
                        pending.discard(rid)
                time.sleep(0.01)
            eng._alloc.check_invariants()
            st = eng.stats()
            assert st["active_slots"] == 0
            # every remaining block is tree-held; dropping the tree
            # returns the whole pool
            eng._radix.clear()
            eng._alloc.check_invariants()
            assert eng._alloc.free_blocks() == eng._page.num_blocks - 1
        finally:
            eng.shutdown()

    def test_match_fault_degrades_to_cold_prefill(self):
        from ray_tpu.common import faults

        want = _baseline(PROMPTS[:3], [8, 8, 8])
        eng = _engine(prefix_cache="radix")
        try:
            faults.inject("serve.llm.prefix_match", "always")
            got = [eng.generate(p, max_tokens=8) for p in PROMPTS[:3]]
            st = eng.stats()
            eng._alloc.check_invariants()
        finally:
            faults.clear()
            eng.shutdown()
        assert got == want                          # cold, but correct
        assert st["prefix_cache"]["match_faults"] == 3
        assert st["prefix_hits"] == 0

    def test_insert_fault_skips_whole_insert(self):
        from ray_tpu.common import faults

        want = _baseline(PROMPTS[:2], [8, 8])
        eng = _engine(prefix_cache="radix")
        try:
            faults.inject("serve.llm.prefix_insert", "always")
            got = [eng.generate(p, max_tokens=8) for p in PROMPTS[:2]]
            st = eng.stats()
            eng._alloc.check_invariants()
        finally:
            faults.clear()
            eng.shutdown()
        assert got == want
        assert st["prefix_cache"]["insert_faults"] >= 2
        assert st["prefix_cache"]["cached_blocks"] == 0

    def test_byte_budget_alone_turns_the_cache_on_and_bounds_it(self):
        """A byte budget with no mode named is the radix cache, and the
        engine's tree never holds more than the budget: two blocks'
        worth here, under four prompts that would cache four each."""
        itemsize = np.dtype(CFG.dtype).itemsize
        block = 2 * CFG.n_layers * 8 * CFG.n_kv_heads * CFG.head_dim * itemsize
        eng = _engine(prefix_cache_bytes=2 * block)
        try:
            got = [eng.generate(p, max_tokens=4) for p in PROMPTS[:4]]
            st = eng.stats()
            eng._alloc.check_invariants()
        finally:
            eng.shutdown()
        assert got == _baseline(PROMPTS[:4], [4] * 4)
        pc = st["prefix_cache"]
        assert pc["mode"] == "radix" and pc["budget_bytes"] == 2 * block
        assert 0 < pc["cached_bytes"] <= 2 * block
        assert st["prefix_hits"] >= 1               # SYSTEM's first blocks

    def test_a_mode_that_is_not_radix_or_off_is_refused(self):
        with pytest.raises(ValueError, match="'radix' or 'off'"):
            _engine(prefix_cache="legacy")


class TestTenantFairShare:
    def _stopped_engine(self, **kw):
        eng = _engine(**kw)
        eng._stop.set()
        eng._thread.join(timeout=10)
        return eng

    def test_pick_waiting_prefers_undershare_tenant(self):
        from ray_tpu.serve.llm import _Request

        eng = self._stopped_engine(prefix_cache="off", num_slots=2)
        ra = _Request([1], 4, 0.0, None, tenant="a")
        eng._slots[0] = ra                          # a holds 1 of 2
        a2 = _Request([2], 4, 0.0, None, tenant="a")
        b1 = _Request([3], 4, 0.0, None, tenant="b")
        eng._waiting.extend([a2, b1])
        # share = 2 slots / 2 tenants = 1; a is AT share, b under it
        assert eng._pick_waiting() == 1
        assert eng._fair_share_skips == 1

    def test_pick_waiting_work_conserving_and_resume_priority(self):
        from ray_tpu.serve.llm import _Request

        eng = self._stopped_engine(prefix_cache="off", num_slots=2)
        a2 = _Request([2], 4, 0.0, None, tenant="a")
        a3 = _Request([3], 4, 0.0, None, tenant="a")
        eng._slots[0] = _Request([1], 4, 0.0, None, tenant="a")
        eng._waiting.extend([a2, a3])
        # single tenant over share: plain FIFO, no starvation
        assert eng._pick_waiting() == 0
        # a preempted request (non-empty output) always resumes first
        pre = _Request([4], 8, 0.0, None, tenant="b")
        pre.output.append(7)
        eng._waiting.clear()
        eng._waiting.extend([pre, a2])
        assert eng._pick_waiting() == 0

    def test_tenant_burst_all_answered(self):
        """One tenant floods, another trickles: everything completes and
        the flood cannot monopolize cache-insert budget (the trickling
        tenant's prefix still gets cached)."""
        eng = _engine(prefix_cache="radix")
        got, errs = {}, []

        def client(i, tenant):
            try:
                got[i] = eng.generate(PROMPTS[i % len(PROMPTS)],
                                      max_tokens=6, tenant=tenant,
                                      timeout_s=240)
            except Exception as e:  # noqa: BLE001
                errs.append((i, e))

        try:
            threads = [threading.Thread(target=client, args=(i, "flood"))
                       for i in range(8)]
            threads.append(threading.Thread(
                target=client, args=(100, "trickle")))
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240)
            st = eng.stats()
            eng._alloc.check_invariants()
        finally:
            eng.shutdown()
        assert not errs, errs
        assert len(got) == 9
        tb = eng._radix.tenant_blocks
        cap = max(1, eng._radix.budget_blocks() // 2)
        assert all(v <= cap for v in tb.values()), tb


class TestServeSurface:
    def test_engine_digest_covers_served_prefix(self):
        from ray_tpu.serve.handle import _RouterState

        eng = _engine(prefix_cache="radix", kv_block_size=16,
                      max_seq=64, num_slots=2)
        try:
            prompt = list(range(33))                # caches 32 tokens
            eng.generate(prompt, max_tokens=4)
            dig = set(eng.prefix_digest())
        finally:
            eng.shutdown()
        want = set(_RouterState._prefix_hashes(prompt[:32]))
        assert want <= dig

    def test_router_digest_tier_and_saturation_fallback(self):
        from ray_tpu.serve.handle import _RouterState

        st = _RouterState("d", controller=None)
        st.replicas = ["r0", "r1", "r2"]
        st.outstanding = {0: 0, 1: 0, 2: 0}
        st.max_ongoing = 4
        st.router = "prefix_aware"
        st.last_refresh = float("inf")
        key = list(range(64))
        # replica 2 advertises the 32-token prefix
        h = _RouterState._prefix_hashes(key[:32])[0]
        st._apply_digests({2: [h]})
        _, idx = st.acquire_replica(key)
        assert idx == 2                             # advert wins over pow2
        for _ in range(3):
            st.acquire_replica(key)
        _, other = st.acquire_replica(key)          # advertiser saturated
        assert other != 2

    def test_replica_harness_digest_passthrough(self):
        from ray_tpu.serve.controller import Replica

        class WithDigest:
            def __call__(self):
                return 1

            def prefix_digest(self):
                return [7, 8]

        class Boom:
            def prefix_digest(self):
                raise RuntimeError("torn walk")

        import cloudpickle

        r = Replica(cloudpickle.dumps(WithDigest), (), {})
        assert r.get_prefix_digest() == [7, 8]
        assert Replica(cloudpickle.dumps(Boom), (), {})\
            .get_prefix_digest() == []
        assert Replica(cloudpickle.dumps(dict), (), {})\
            .get_prefix_digest() == []

    def test_schema_validates_prefix_cache_args(self):
        from ray_tpu.serve import schema

        cfg = {"applications": [{
            "name": "llm",
            "import_path": "ray_tpu.serve.api:llm_app",
            "args": {"model": "debug", "prefix_cache": "radix",
                     "prefix_cache_bytes": "4096"},
        }]}
        out = schema.validate_config(cfg)
        assert out["applications"][0]["args"]["prefix_cache_bytes"] == 4096
        for refused in ("bogus", "legacy"):
            cfg["applications"][0]["args"]["prefix_cache"] = refused
            with pytest.raises(schema.ServeConfigError,
                               match=r"prefix_cache"):
                schema.validate_config(cfg)
        cfg["applications"][0]["args"]["prefix_cache"] = "off"
        cfg["applications"][0]["args"]["prefix_cache_bytes"] = -5
        with pytest.raises(schema.ServeConfigError,
                           match=r"prefix_cache_bytes"):
            schema.validate_config(cfg)
