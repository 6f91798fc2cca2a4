"""Paged KV cache: allocator, kernel-vs-oracle, and equivalence with the
slot-based decoding pipeline (same greedy tokens on the debug model)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.decoding import (
    init_cache, make_decode_step, make_prefill)
from ray_tpu.models.paged_cache import (
    BlockAllocator, PagedConfig, extract_kv, init_paged_cache,
    make_paged_decode_step, make_paged_inject, make_paged_prefill,
    pad_to_block_bucket)


@pytest.fixture(scope="module")
def cfg():
    return llama.CONFIGS["debug"]


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(cfg, jax.random.key(0))


class TestAllocator:
    def test_alloc_release_cycle(self):
        page = PagedConfig(num_blocks=9, block_size=4, max_seq=32)
        al = BlockAllocator(page, num_slots=2)
        assert al.free_blocks() == 8
        assert al.ensure(0, 10)          # 3 blocks
        assert al.free_blocks() == 5
        assert al.ensure(0, 12)          # still 3 blocks
        assert al.free_blocks() == 5
        assert al.ensure(0, 13)          # 4th block
        assert al.free_blocks() == 4
        # distinct physical blocks, none the null block
        ids = al.tables[0, :4]
        assert len(set(ids.tolist())) == 4 and 0 not in ids
        al.release(0)
        assert al.free_blocks() == 8
        assert (al.tables[0] == 0).all()

    def test_pool_exhaustion_refused(self):
        page = PagedConfig(num_blocks=5, block_size=4, max_seq=64)
        al = BlockAllocator(page, num_slots=2)
        assert al.ensure(0, 16)          # all 4 usable blocks
        assert not al.ensure(1, 4)       # nothing left
        assert al.free_blocks() == 0
        al.release(0)
        assert al.ensure(1, 4)

    def test_max_seq_cap(self):
        page = PagedConfig(num_blocks=64, block_size=4, max_seq=16)
        al = BlockAllocator(page, num_slots=1)
        assert not al.ensure(0, 17)      # over max_blocks_per_seq

    def test_pad_to_block_bucket(self):
        assert pad_to_block_bucket(3, 64) == 64
        assert pad_to_block_bucket(65, 64) == 128
        # beyond the largest bucket: round to a bucket-sized multiple
        # (bounds the number of compiled prefill shapes)
        assert pad_to_block_bucket(4000, 64) == 4096


def test_the_slot_reservation_answers_as_an_allocator_that_never_lacks():
    """The slot cache behind the paged interface: ``max_seq`` rows a slot
    from the start, so a sequence that long fits and one longer never
    will, nothing is lacking, grown, trimmed or given back, and there is
    no table and no block to report."""
    from ray_tpu.models.serving import SlotReservation

    alloc = SlotReservation(max_seq=64)
    assert alloc.fits(64) and not alloc.fits(65)
    assert alloc.lacking(64, shared=0, headroom=8) == 0
    assert alloc.ensure(3, 64) is True and alloc.trim(3, 64) == 0
    assert alloc.release(3) is None and alloc.ensure(3, 1) is True
    assert alloc.table_rows(3) is None and alloc.device_tables() is None
    assert alloc.free_blocks() is None and alloc.pools([5, 9]) == {}
    # what the one-pool allocator answers to the same two plain calls
    one_pool = BlockAllocator(PagedConfig(num_blocks=5, block_size=8,
                                          max_seq=32), num_slots=2)
    assert one_pool.ensure(0, 9) and one_pool.trim(0, 9) == 0
    assert one_pool.pools([9]) == {} and one_pool.free_blocks() == 2


class TestKernelVsOracle:
    def test_paged_kernel_interpret_matches_reference(self):
        from ray_tpu.ops.pallas.paged_decode_attention import (
            paged_attention_reference, paged_decode_attention)

        B, H, KV, D, NB, bs, MBS = 2, 4, 2, 16, 7, 16, 3
        k1, k2, k3, k4 = jax.random.split(jax.random.key(1), 4)
        q = jax.random.normal(k1, (B, 1, H, D), jnp.float32)
        kp = jax.random.normal(k2, (NB, bs, KV * D), jnp.float32)
        vp = jax.random.normal(k3, (NB, bs, KV * D), jnp.float32)
        # slot 0 uses blocks [3, 5], slot 1 blocks [1, 2, 6]
        tables = jnp.array([[3, 5, 0], [1, 2, 6]], jnp.int32)
        lengths = jnp.array([20, 41], jnp.int32)
        want = paged_attention_reference(q, kp[None], vp[None], 0, tables,
                                         lengths, scale=D ** -0.5)
        got = paged_decode_attention(q, kp[None], vp[None], 0, tables,
                                     lengths, scale=D ** -0.5,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("bs", [16, 64])
    def test_kernel_reads_the_layer_it_is_given(self, bs):
        """A pool of three layers, each with other rows: the kernel,
        given the whole pool and a traced layer index, attends over
        that layer's blocks and no other's."""
        from ray_tpu.ops.pallas.paged_decode_attention import (
            paged_attention_reference, paged_decode_attention)

        L, B, H, KV, D, NB, MBS = 3, 2, 4, 2, 128, 7, 3
        k1, k2, k3 = jax.random.split(jax.random.key(2), 3)
        q = jax.random.normal(k1, (B, 1, H, D), jnp.float32)
        kp = jax.random.normal(k2, (L, NB, bs, KV * D), jnp.float32)
        vp = jax.random.normal(k3, (L, NB, bs, KV * D), jnp.float32)
        tables = jnp.array([[3, 5, 0], [1, 2, 6]], jnp.int32)
        lengths = jnp.array([bs + 4, 2 * bs + 9], jnp.int32)
        kernel = jax.jit(lambda l: paged_decode_attention(
            q, kp, vp, l, tables, lengths, scale=D ** -0.5,
            interpret=True))
        outs = []
        for layer in (2, 1):
            # the oracle on that layer alone, as a pool of one layer
            want = paged_attention_reference(
                q, kp[layer][None], vp[layer][None], 0, tables, lengths,
                scale=D ** -0.5)
            ref = paged_attention_reference(q, kp, vp, layer, tables,
                                            lengths, scale=D ** -0.5)
            np.testing.assert_array_equal(np.asarray(ref),
                                          np.asarray(want))
            got = kernel(jnp.int32(layer))
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-3, atol=2e-3)
            outs.append(np.asarray(got))
        assert np.abs(outs[0] - outs[1]).max() > 1e-2   # layers differ

    def test_a_row_does_not_divide_into_the_querys_width(self):
        from ray_tpu.ops.pallas.paged_decode_attention import paged_decode

        q = jnp.zeros((1, 1, 4, 16), jnp.float32)
        pool = jnp.zeros((1, 2, 8, 40), jnp.float32)
        with pytest.raises(ValueError, match="pool rows of 40"):
            paged_decode(q, pool, pool, 0, jnp.zeros((1, 1), jnp.int32),
                         jnp.ones((1,), jnp.int32), scale=1.0)


# lengths of four slots over a table of three blocks, as multiples of the
# block size ``bs`` plus a rest: (blocks, rest) -> blocks * bs + rest
RAGGED = {
    "empty-slot-between-running": [(1, 3), (0, 0), (2, 1), (0, 7)],
    "length-one": [(0, 1), (1, 1), (0, 0), (2, 1)],
    "whole-blocks": [(1, 0), (2, 0), (0, 0), (3, 0)],
    "full-table": [(3, 0), (0, 5), (3, 0), (2, 15)],
    "every-slot-empty": [(0, 0)] * 4,
}
# the same over a table of SIX blocks, which the kernel's four blocks a
# step do not divide: a slot past four blocks takes a second step whose
# last blocks lie past the table's end
RAGGED_TWO_STEPS = {
    "one-past-a-step": [(4, 1), (0, 0), (4, 0), (3, 15)],
    "one-past-a-block": [(1, 1), (1, 0), (5, 1), (0, 0)],
    "last-step-past-the-table": [(6, 0), (5, 3), (0, 0), (5, 0)],
    "every-table-full": [(6, 0)] * 4,
}
# the three dense cells' kernel shapes: query rows, KV heads, head width
# (the block step's rows are 4 positions x 32 heads, KV-major)
CELL_SHAPES = {"mistral": (32, 8, 128), "block-step": (128, 4, 128),
               "two-kv-heads": (8, 2, 128)}


def _ragged_case(case, bs, shape=(4, 2, 16)):
    """q, a one-layer pool, tables and lengths of a ragged case: each slot
    owns distinct blocks for its live pairs, the null block past them."""
    H, KV, D = shape
    spans = RAGGED.get(case) or RAGGED_TWO_STEPS[case]
    B, MBS = 4, 3 if case in RAGGED else 6
    NB = B * MBS + 2
    lengths = np.array([n * bs + r for n, r in spans], np.int32)
    tables = np.zeros((B, MBS), np.int32)
    ids = iter(np.random.default_rng(3).permutation(np.arange(1, NB)))
    for s, n in enumerate(lengths):
        for j in range(-(-int(n) // bs)):
            tables[s, j] = next(ids)
    k1, k2, k3 = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(k1, (B, 1, H, D), jnp.float32)
    kp = jax.random.normal(k2, (1, NB, bs, KV * D), jnp.float32)
    vp = jax.random.normal(k3, (1, NB, bs, KV * D), jnp.float32)
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths)


def _by_head_loop(q, kp, vp, tables, lengths, scale, kv_of_row):
    """Plain numpy, a query row at a time: row r of slot s against the
    ``D`` columns of KV head ``kv_of_row(r)`` in the slot's first
    ``lengths[s]`` token rows."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    B, _, H, D = q.shape
    bs = kp.shape[2]
    out = np.zeros((B, 1, H, D))
    for s, n in enumerate(np.asarray(lengths)):
        if not n:
            continue
        k_rows, v_rows = (p[0, np.asarray(tables)[s]].reshape(
            -1, p.shape[-1])[:n] for p in (kp, vp))
        for r in range(H):
            cols = slice(kv_of_row(r) * D, (kv_of_row(r) + 1) * D)
            logit = k_rows[:, cols] @ q[s, 0, r] * scale
            w = np.exp(logit - logit.max())
            out[s, 0, r] = w @ v_rows[:, cols] / w.sum()
    return out


class TestKernelWalksLiveBlocksOnly:
    """The kernel takes a step for a run of a slot's blocks only if one
    of them holds cached tokens: the work list is exactly those, a slot
    of length 0 gets a row of zeros, and no other block is read."""

    @pytest.mark.parametrize("bs", [16, 64])
    @pytest.mark.parametrize("case", [*RAGGED, *RAGGED_TWO_STEPS])
    def test_ragged_lengths_match_the_oracle(self, case, bs):
        from ray_tpu.ops.pallas.paged_decode_attention import (
            paged_attention_reference, paged_decode_attention)

        q, kp, vp, tables, lengths = _ragged_case(case, bs)
        want = np.asarray(paged_attention_reference(
            q, kp, vp, 0, tables, lengths, scale=0.25))
        got = np.asarray(paged_decode_attention(
            q, kp, vp, 0, tables, lengths, scale=0.25, interpret=True))
        live = np.asarray(lengths) > 0
        np.testing.assert_allclose(got[live], want[live],
                                   rtol=2e-3, atol=2e-3)
        assert not got[~live].any()              # zeros, not garbage

    @pytest.mark.parametrize("shape", CELL_SHAPES)
    @pytest.mark.parametrize("case", ["empty-slot-between-running",
                                      "last-step-past-the-table"])
    def test_the_dispatcher_at_the_cells_widths(self, case, shape,
                                                kernel_on_cpu):
        """``paged_decode`` as the programs call it (the kernel, its
        work list handed in) at each dense cell's query rows, KV heads
        and head width, against the oracle and against a loop over
        query rows in which row r reads KV head ``r // (H // KV)``: in
        the block step's order, position-major inside a KV head, that
        is ``r // (block_length * group)``."""
        from ray_tpu.ops.pallas.paged_decode_attention import (
            paged_attention_reference, paged_decode, paged_decode_work)

        H, KV, D = CELL_SHAPES[shape]
        q, kp, vp, tables, lengths = _ragged_case(case, 16, (H, KV, D))
        work = paged_decode_work(lengths, 16, tables.shape[1])
        assert work is not None
        got = np.asarray(paged_decode(q, kp, vp, 0, tables, lengths,
                                      scale=D ** -0.5, work=work))
        want = np.asarray(paged_attention_reference(
            q, kp, vp, 0, tables, lengths, scale=D ** -0.5))
        live = np.asarray(lengths) > 0       # an empty slot's row: zeros
        np.testing.assert_allclose(got[live], want[live],
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(
            got, _by_head_loop(q, kp, vp, tables, lengths, D ** -0.5,
                               lambda r: r // (H // KV)),
            rtol=2e-3, atol=2e-3)

    def test_block_step_rows_reach_their_own_kv_head(self, kernel_on_cpu):
        """The block step's query (S, B, H, D) in the order it hands the
        kernel (KV head, position, head in the group) and back: every
        position's every head gets what that head gets alone."""
        from ray_tpu.ops.pallas.paged_decode_attention import paged_decode

        Bl, KV, g, D, bs = 4, 2, 2, 16, 16
        _, kp, vp, tables, lengths = _ragged_case(
            "one-past-a-step", bs, (KV * g, KV, D))
        S = tables.shape[0]
        q = jax.random.normal(jax.random.key(9), (S, Bl, KV * g, D))
        qk = q.reshape(S, Bl, KV, g, D).transpose(0, 2, 1, 3, 4)
        out = paged_decode(qk.reshape(S, 1, KV * Bl * g, D), kp, vp, 0,
                           tables, lengths, scale=0.25)
        out = np.asarray(out).reshape(S, KV, Bl, g, D).transpose(
            0, 2, 1, 3, 4).reshape(S, Bl, KV * g, D)
        for b in range(Bl):
            alone = paged_decode(q[:, b][:, None], kp, vp, 0, tables,
                                 lengths, scale=0.25)
            np.testing.assert_allclose(out[:, b], np.asarray(alone)[:, 0],
                                       rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("case", ["empty-slot-between-running",
                                      "full-table", "every-slot-empty",
                                      "last-step-past-the-table"])
    def test_no_block_outside_the_work_list_is_read(self, case):
        from ray_tpu.ops.pallas.paged_decode_attention import (
            paged_decode_attention)

        bs = 16
        q, kp, vp, tables, lengths = _ragged_case(case, bs)
        named = {int(tables[s, j]) for s, n in enumerate(np.asarray(lengths))
                 for j in range(-(-int(n) // bs))}
        dead = np.array([b not in named for b in range(kp.shape[1])])
        assert dead[0]                           # the null block among them
        poison = lambda pool: pool.at[:, dead].set(jnp.nan)  # noqa: E731
        run = lambda k, v: np.asarray(paged_decode_attention(  # noqa: E731
            q, k, v, 0, tables, lengths, scale=0.25, interpret=True))
        got = run(poison(kp), poison(vp))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, run(kp, vp))

    @pytest.mark.parametrize("lengths, bs, mbs", [
        ([19, 0, 33, 7], 16, 3), ([0, 0, 0], 16, 4), ([64, 128, 1], 64, 2),
        ([48, 48, 48, 48], 16, 3), ([0, 5], 64, 64), ([700, 9], 64, 4)])
    def test_work_list_is_the_live_pairs_in_slot_order(self, lengths, bs,
                                                       mbs):
        from ray_tpu.ops.pallas.paged_decode_attention import (
            decode_work_list)

        n_work, slot, block = jax.jit(
            decode_work_list, static_argnums=(1, 2))(
                jnp.asarray(lengths, jnp.int32), bs, mbs)
        want = [(s, j) for s, n in enumerate(lengths)
                for j in range(min(-(-n // bs), mbs))]
        assert int(n_work) == len(want)
        assert slot.shape == block.shape == (len(lengths) * mbs + 1,)
        got = list(zip(np.asarray(slot).tolist(), np.asarray(block).tolist()))
        assert got[:len(want)] == want
        # past the list: the last pair again (or (last slot, 0) of an empty
        # list), so an index map that runs ahead names a real block
        assert set(got[len(want):]) <= {want[-1] if want
                                        else (len(lengths) - 1, 0)}

    @pytest.mark.parametrize("lengths, bs, mbs", [
        ([19, 0, 33, 7], 16, 3), ([96, 96, 96], 16, 6), ([65, 64, 1], 16, 6),
        ([0, 0], 16, 5), ([2048] * 3, 64, 32), ([700, 9], 64, 4)])
    def test_the_dispatchers_list_is_every_fourth_pair(self, lengths, bs,
                                                       mbs, kernel_on_cpu,
                                                       monkeypatch):
        """``paged_decode_work``: the list the kernel walks, a slot's
        blocks four a step (all of a table under four wide), as long as
        a full batch's steps and the lookahead's one more; None where
        the oracle attends."""
        from ray_tpu.ops import attention
        from ray_tpu.ops.pallas.paged_decode_attention import (
            paged_decode_work)

        G = min(4, mbs)
        n_work, slot, block = paged_decode_work(
            jnp.asarray(lengths, jnp.int32), bs, mbs)
        want = [(s, j) for s, n in enumerate(lengths)
                for j in range(0, min(-(-n // bs), mbs), G)]
        assert int(n_work) == len(want)
        assert slot.shape == block.shape == (len(lengths) * -(-mbs // G)
                                             + 1,)
        got = list(zip(np.asarray(slot).tolist(), np.asarray(block).tolist()))
        assert got[:len(want)] == want
        assert set(got[len(want):]) <= {want[-1] if want
                                        else (len(lengths) - 1, 0)}
        monkeypatch.setattr(attention, "on_tpu", lambda: False)
        assert paged_decode_work(jnp.asarray(lengths), bs, mbs) is None


class TestWritesInPlace:
    """The programs carry the whole pool and write into it: a step or a
    prefill changes the rows it is meant to, in every layer, and leaves
    every other row of every block bit-identical (the null block, where
    padding and inactive slots land, aside)."""

    @staticmethod
    def _noise_cache(cfg, page, num_slots):
        cache = init_paged_cache(cfg, page, num_slots)
        k1, k2 = jax.random.split(jax.random.key(7))
        shape = cache["k"].shape
        return {"k": jax.random.normal(k1, shape, cache["k"].dtype),
                "v": jax.random.normal(k2, shape, cache["v"].dtype),
                "length": cache["length"]}

    def test_prefill_touches_only_the_prompts_blocks(self, cfg, params):
        page = PagedConfig(num_blocks=9, block_size=16, max_seq=64)
        al = BlockAllocator(page, num_slots=2)
        al.ensure(1, 16)                     # a neighbour's block
        al.ensure(0, 20)                     # 2 blocks for the prompt
        mine = al.tables[0, :2].tolist()
        cache = self._noise_cache(cfg, page, 2)
        before = {n: np.asarray(cache[n]) for n in ("k", "v")}
        tokens = np.zeros((1, 32), np.int32)
        tokens[0, :20] = np.arange(1, 21)
        cache, _ = make_paged_prefill(params, cfg, page)(
            cache, al.tables[0], jnp.asarray(tokens), 20, 0)
        others = [b for b in range(1, page.num_blocks) if b not in mine]
        for n in ("k", "v"):
            after = np.asarray(cache[n])
            np.testing.assert_array_equal(after[:, others],
                                          before[n][:, others])
            # every layer got the prompt's rows, and zeros past them
            rows = after[:, mine].reshape(cfg.n_layers, 32, -1)
            assert (rows[:, :20] != before[n][:, mine].reshape(
                cfg.n_layers, 32, -1)[:, :20]).any(axis=(1, 2)).all()
            assert not rows[:, 20:].any()
        assert int(cache["length"][0]) == 20

    def test_decode_step_touches_one_row_a_slot(self, cfg, params):
        page = PagedConfig(num_blocks=9, block_size=16, max_seq=64)
        al = BlockAllocator(page, num_slots=3)
        lengths = [5, 17, 3]                 # slot 2 is inactive
        for slot, n in enumerate(lengths):
            al.ensure(slot, n + 1)
        cache = self._noise_cache(cfg, page, 3)
        cache["length"] = jnp.asarray(lengths, jnp.int32)
        before = {n: np.asarray(cache[n]) for n in ("k", "v")}
        active = np.array([True, True, False])
        cache, logits = make_paged_decode_step(params, cfg, page)(
            cache, al.device_tables(), jnp.asarray([7, 8, 9], jnp.int32),
            jnp.asarray(active))
        assert np.isfinite(np.asarray(logits)[:2]).all()
        written = {(int(al.tables[s, n // 16]), n % 16)
                   for s, n in enumerate(lengths) if active[s]}
        same = np.ones(before["k"].shape[1:3], bool)   # (NB, bs)
        same[0] = False                                # null block
        for blk, off in written:
            same[blk, off] = False
        for n in ("k", "v"):
            after = np.asarray(cache[n])
            np.testing.assert_array_equal(after[:, same],
                                          before[n][:, same])
            for blk, off in written:                   # in every layer
                assert (after[:, blk, off] != before[n][:, blk, off]
                        ).any(axis=1).all()
        np.testing.assert_array_equal(np.asarray(cache["length"]),
                                      [6, 18, 3])

    def test_inactive_slots_stale_length_is_not_attended(self, cfg, params):
        """Nothing resets ``cache["length"]`` when a slot is released: two
        of four slots are inactive with a stale length, and the step gives
        the active slots' logits bit for bit as with those lengths at 0,
        keeps the stale lengths, and writes for them only into the null
        block."""
        page = PagedConfig(num_blocks=12, block_size=16, max_seq=64)
        al = BlockAllocator(page, num_slots=4)
        active = np.array([True, False, True, False])
        for slot, n in ((0, 21), (2, 6)):
            al.ensure(slot, n + 1)
        step = make_paged_decode_step(params, cfg, page)
        tokens = jnp.asarray([7, 8, 9, 10], jnp.int32)
        outs = {}
        for name, lengths in (("stale", [21, 37, 6, 50]),
                              ("zero", [21, 0, 6, 0])):
            cache = self._noise_cache(cfg, page, 4)
            cache["length"] = jnp.asarray(lengths, jnp.int32)
            before = {n: np.asarray(cache[n]) for n in ("k", "v")}
            cache, logits = step(cache, al.device_tables(), tokens,
                                 jnp.asarray(active))
            outs[name] = np.asarray(logits)
            np.testing.assert_array_equal(
                np.asarray(cache["length"]),
                np.where(active, np.asarray(lengths) + 1, lengths))
            changed = {n: (np.asarray(cache[n]) != before[n]).any(
                axis=(0, 3)) for n in ("k", "v")}          # (NB, bs)
            live = {(int(al.tables[s, lengths[s] // 16]), lengths[s] % 16)
                    for s in (0, 2)}
            for n in ("k", "v"):
                rows = {(int(b), int(o)) for b, o in np.argwhere(changed[n])
                        if b != 0}
                assert rows == live
        assert np.isfinite(outs["stale"][active]).all()
        np.testing.assert_array_equal(outs["stale"][active],
                                      outs["zero"][active])


class TestPagedEqualsSlot:
    def test_greedy_tokens_match_slot_pipeline(self, cfg, params):
        """Prefill + 8 greedy decode steps: the paged pipeline must emit
        exactly the slot pipeline's tokens, with the prompt's blocks
        deliberately non-contiguous and out of order."""
        num_slots = 2
        page = PagedConfig(num_blocks=17, block_size=16, max_seq=256)
        al = BlockAllocator(page, num_slots)

        prompt = list(range(1, 13))          # 12 tokens
        P = pad_to_block_bucket(len(prompt), page.block_size,
                                buckets=(16, 32, 64))
        tokens = np.zeros((1, P), np.int32)
        tokens[0, :len(prompt)] = prompt

        # slot pipeline
        s_cache = init_cache(cfg, num_slots, max_seq=256)
        s_prefill = make_prefill(params, cfg)
        s_decode = make_decode_step(params, cfg)
        s_cache, s_logits = s_prefill(s_cache, jnp.asarray(tokens),
                                      len(prompt), 0)
        s_toks = [int(jnp.argmax(s_logits))]
        last = np.zeros(num_slots, np.int32)
        active = np.zeros(num_slots, bool)
        active[0] = True
        last[0] = s_toks[0]
        for _ in range(8):
            s_cache, lg = s_decode(s_cache, jnp.asarray(last),
                                   jnp.asarray(active))
            t = int(jnp.argmax(lg[0]))
            s_toks.append(t)
            last[0] = t

        # paged pipeline: fragment the free list so the prompt's blocks
        # are non-contiguous and out of order
        al.ensure(1, 3 * page.block_size)   # grab blocks for slot 1
        al.ensure(0, len(prompt))
        al.release(1)                        # free a hole BELOW slot 0's
        p_cache = init_paged_cache(cfg, page, num_slots)
        p_prefill = make_paged_prefill(params, cfg, page)
        p_decode = make_paged_decode_step(params, cfg, page)
        p_cache, p_logits = p_prefill(p_cache, al.tables[0],
                                      jnp.asarray(tokens), len(prompt), 0)
        p_toks = [int(jnp.argmax(p_logits))]
        last = np.zeros(num_slots, np.int32)
        last[0] = p_toks[0]
        for _ in range(8):
            al.ensure(0, len(prompt) + len(p_toks) + 1)
            p_cache, lg = p_decode(p_cache, al.device_tables(),
                                   jnp.asarray(last), jnp.asarray(active))
            t = int(jnp.argmax(lg[0]))
            p_toks.append(t)
            last[0] = t

        assert p_toks == s_toks

    def test_inject_extract_roundtrip(self, cfg, params):
        """extract_kv of a prefilled slot re-injected into another slot
        yields the same next-token logits."""
        num_slots = 2
        page = PagedConfig(num_blocks=9, block_size=16, max_seq=128)
        al = BlockAllocator(page, num_slots)
        prompt = list(range(5, 25))          # 20 tokens
        P = pad_to_block_bucket(len(prompt), page.block_size,
                                buckets=(32, 64))
        tokens = np.zeros((1, P), np.int32)
        tokens[0, :len(prompt)] = prompt

        al.ensure(0, len(prompt))
        cache = init_paged_cache(cfg, page, num_slots)
        prefill = make_paged_prefill(params, cfg, page)
        decode = make_paged_decode_step(params, cfg, page)
        inject = make_paged_inject(cfg, page)
        cache, logits0 = prefill(cache, al.tables[0], jnp.asarray(tokens),
                                 len(prompt), 0)
        k, v = extract_kv(cache, al, 0, len(prompt))
        assert k.shape == (cfg.n_layers, len(prompt),
                           cfg.n_kv_heads * cfg.head_dim)

        # inject into slot 1 (pad rows to a block multiple, zeros beyond)
        pad = P - len(prompt)
        kp = np.pad(k, ((0, 0), (0, pad), (0, 0)))
        vp = np.pad(v, ((0, 0), (0, pad), (0, 0)))
        al.ensure(1, len(prompt))
        cache = inject(cache, al.tables[1], kp, vp, len(prompt), 1)

        tok = int(jnp.argmax(logits0))
        last = np.array([tok, tok], np.int32)
        al.ensure(0, len(prompt) + 1)
        al.ensure(1, len(prompt) + 1)
        cache, lg = decode(cache, al.device_tables(), jnp.asarray(last),
                           jnp.asarray([True, True]))
        np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(lg[1]),
                                   rtol=1e-4, atol=1e-4)


class TestPagedEngine:
    """LLMEngine with kv_cache='paged': correctness vs the slot engine,
    capacity at equal HBM, and recompute preemption."""

    def _engine(self, **kw):
        from ray_tpu.serve.llm import LLMEngine

        return LLMEngine(model="debug", **kw)

    # the second prompt is EXACTLY one block (16 tokens at bs=16): its
    # first decoded token's KV lands in a block allocated at admission,
    # not the null block (regression: block-aligned prompts corrupted
    # the first post-prompt position)
    @pytest.mark.parametrize("prompt", [
        [5, 17, 99, 3, 42],
        list(range(2, 18)),
    ])
    def test_paged_engine_matches_slot_engine(self, prompt):
        slot_e = self._engine(num_slots=2, max_seq=128, kv_cache="slot")
        try:
            want = slot_e.generate(prompt, max_tokens=8, timeout_s=120)
        finally:
            slot_e.shutdown()
        paged_e = self._engine(num_slots=2, max_seq=128,
                               kv_cache="paged", kv_block_size=16)
        try:
            got = paged_e.generate(prompt, max_tokens=8, timeout_s=120)
            assert paged_e.stats()["kv_cache"] == "paged"
        finally:
            paged_e.shutdown()
        assert got == want

    def test_double_concurrency_at_equal_hbm(self):
        """The capacity claim: with the SAME total KV HBM as a 2-slot
        slot-cache engine (2 x max_seq tokens), the paged engine runs 4
        short requests CONCURRENTLY (the slot engine's ceiling is 2)."""
        import threading

        max_seq = 256
        eng = self._engine(num_slots=4, max_seq=max_seq,
                           kv_cache="paged", kv_block_size=16,
                           kv_pool_tokens=2 * max_seq)
        seen = []

        def run(i):
            out = eng.generate([3 + i, 7, 11], max_tokens=24,
                               timeout_s=120)
            seen.append(out)

        try:
            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(4)]
            peak = 0
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads):
                peak = max(peak, eng.stats()["active_slots"])
            for t in threads:
                t.join()
            assert len(seen) == 4
            assert peak > 2, (
                f"paged engine never exceeded the slot ceiling: {peak}")
            assert eng.stats()["preemptions"] == 0
        finally:
            eng.shutdown()

    def test_preemption_under_pool_pressure(self):
        """Pool smaller than the aggregate demand: requests must still
        all complete, via recompute preemption."""
        import threading

        eng = self._engine(num_slots=3, max_seq=256, kv_cache="paged",
                           kv_block_size=16, kv_pool_tokens=96)
        outs = {}

        def run(i):
            outs[i] = eng.generate([2 + i, 9, 4], max_tokens=40,
                                   timeout_s=180)

        try:
            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert sorted(outs) == [0, 1, 2]
            assert all(len(v) == 40 for v in outs.values())
            st = eng.stats()
            assert st["preemptions"] >= 1, st
        finally:
            eng.shutdown()

    def test_preempted_request_output_consistent(self):
        """A preempted+resumed greedy request must produce the same
        tokens as an unpressured run (recompute is exact)."""
        eng1 = self._engine(num_slots=1, max_seq=256, kv_cache="paged",
                            kv_block_size=16)
        try:
            want = eng1.generate([5, 6, 7], max_tokens=40, timeout_s=120)
        finally:
            eng1.shutdown()

        import threading

        eng = self._engine(num_slots=3, max_seq=256, kv_cache="paged",
                           kv_block_size=16, kv_pool_tokens=96)
        outs = {}

        def run(i):
            outs[i] = eng.generate([5, 6, 7], max_tokens=40,
                                   timeout_s=180)

        try:
            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            eng.shutdown()
        for i in range(3):
            assert outs[i] == want, f"request {i} diverged"

    def test_oversize_prompt_fails_cleanly(self):
        eng = self._engine(num_slots=2, max_seq=128, kv_cache="paged",
                           kv_block_size=16, kv_pool_tokens=64)
        try:
            with pytest.raises(RuntimeError, match="exceeds KV pool"):
                eng.generate(list(range(1, 100)), max_tokens=8,
                             timeout_s=120)
            # engine still serves admissible requests afterwards
            out = eng.generate([4, 5], max_tokens=4, timeout_s=120)
            assert len(out) == 4
        finally:
            eng.shutdown()

    def test_a_finished_request_has_given_its_blocks_back(self):
        """``generate`` returns only once the slot's blocks are free again:
        the engine announces a request done after the release, not before
        (a release made slow here used to lose that race every time)."""
        import time

        eng = self._engine(num_slots=2, max_seq=128, kv_cache="paged",
                           kv_block_size=16)
        try:
            release = eng._alloc.release

            def slow_release(slot):
                time.sleep(0.3)
                release(slot)

            eng._alloc.release = slow_release
            whole = eng.stats()["kv_blocks_free"]
            assert len(eng.generate([4, 5, 6], max_tokens=4,
                                    timeout_s=120)) == 4
            st = eng.stats()
            assert st["kv_blocks_free"] == whole and st["active_slots"] == 0
        finally:
            eng.shutdown()
