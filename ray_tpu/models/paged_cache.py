"""Paged KV cache: block-table paging for serving (vLLM-class memory
efficiency, TPU-native shapes).

Replaces the slot model's per-slot ``max_seq`` reservation
(:mod:`ray_tpu.models.decoding` keeps a (layers, slots, max_seq, KV, D)
ring) with a shared pool of fixed-size token blocks:

    pool      (layers, num_blocks, block_size, KV * D)   — a token a row
    tables    (slots, max_blocks_per_seq) int32   — host-owned
    lengths   (slots,) int32                       — device-resident

HBM held per request is proportional to tokens actually cached, not to
``max_seq``; a prompt never needs a contiguous region (blocks are
scattered), so fragmentation cannot reject an admissible request.

Division of labor (TPU-first): every step is jitted with static shapes —
the pool and tables never change shape. The BLOCK ALLOCATOR is pure
host-side Python (free-list over block ids); tables are tiny int32
arrays shipped per call. Block 0 is reserved as the null block: table
entries past a slot's valid prefix point at it, and writes for inactive
slots land in it, so no predication is needed on device.

The pool is ONE buffer for the life of the engine. Every program takes
it as a donated argument and returns it updated in place: the decode
step, the prefill and the chunked prefill carry the whole
(layers, num_blocks, ...) pool through their layer scan
(:func:`_scan_layers`) and write a layer's new rows with
``pool.at[l, ...].set``; the decode kernel gets the whole pool and the
layer index; inject, block copy and extract index it by block. No
program moves more of the pool than the rows it writes and the blocks it
attends over, and none holds a second copy
(``tests/test_chip_compile.py`` asks the chip's compiler).

Reference parity: the reference's serving engine gets this from vLLM
(``python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py``);
here it is in-framework. The decode attention rides
:mod:`ray_tpu.ops.pallas.paged_decode_attention` on TPU and its gather
oracle elsewhere.

**Where the block lives.** Its arithmetic is :mod:`ray_tpu.models.llama`'s,
strung into one serving block by :func:`ray_tpu.models.decoding.dense_block`.
A builder here adds which block and offset each row of its call lands at,
and an ``attend``: write the call's K and V rows into the pool at layer
``l``, then attend over the prompt itself (prefill), the blocks the tables
name (decode) or one slot's gathered rows (chunk).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.decoding import (_bind_padded, _bind_params,
                                      attend_rows, dense_block)
from ray_tpu.models.llama import LlamaConfig, Params, embed, logits_f32
from ray_tpu.ops.attention import mha_reference
from ray_tpu.ops.pallas.paged_decode_attention import (paged_decode,
                                                       paged_decode_work)
from ray_tpu.ops.rope import rope_frequencies
from ray_tpu.util.profiling import part

PagedCache = Dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    """Static pool geometry. ``num_blocks`` includes the reserved null
    block 0, so usable KV capacity is (num_blocks - 1) * block_size
    tokens shared by all slots."""

    num_blocks: int
    block_size: int = 64          # (8, 128)-tile friendly for bf16
    max_seq: int = 2048           # longest single sequence admitted

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_seq // self.block_size)


def init_paged_cache(config: LlamaConfig, page: PagedConfig,
                     num_slots: int, dtype=None) -> PagedCache:
    c = config
    dt = dtype or c.dtype
    # lane-dense: a token's KV heads side by side in one row, which is
    # what the decode kernel multiplies and what the chip tiles whole
    shape = (c.n_layers, page.num_blocks, page.block_size,
             c.n_kv_heads * c.head_dim)
    return {
        "k": jnp.zeros(shape, dt),
        "v": jnp.zeros(shape, dt),
        "length": jnp.zeros((num_slots,), jnp.int32),
    }


class BlockAllocator:
    """Host-side free-list allocator + block tables. Not thread-safe:
    owned by the single engine loop, like the rest of the engine state.

    Blocks are ref-counted so the radix prefix cache
    (:mod:`ray_tpu.models.prefix_cache`) can share one physical block
    between the tree and any number of slot tables: ``ensure`` hands out
    private blocks at refcount 1, ``adopt`` aliases already-populated
    shared blocks into a slot's table (incref), and ``release`` only
    returns a block to the free list when its last reference drops.
    A block on the free list always has refcount 0."""

    def __init__(self, page: PagedConfig, num_slots: int):
        self.page = page
        self.num_slots = num_slots
        self._free: List[int] = list(range(page.num_blocks - 1, 0, -1))
        self.tables = np.zeros((num_slots, page.max_blocks_per_seq),
                               np.int32)
        self._owned: List[List[int]] = [[] for _ in range(num_slots)]
        # logical index of a slot's first owned block: 0 unless the
        # blocks behind a window were given back (:meth:`drop_before`)
        self._base = np.zeros(num_slots, np.int64)
        self._ref = np.zeros(page.num_blocks, np.int32)
        self._ref[0] = 1             # null block: pinned forever
        self._device_tables = None   # cache: re-upload only after changes

    def free_blocks(self) -> int:
        return len(self._free)

    def pools(self, slot_lengths=()) -> Dict[str, dict]:
        """One kind of state: no row by kind for ``stats()``."""
        return {}

    def trim(self, slot: int, tokens: int) -> int:
        """No window passes anything: nothing goes back before the end."""
        return 0

    def blocks_for(self, tokens: int) -> int:
        return -(-tokens // self.page.block_size)

    def refcount(self, block: int) -> int:
        return int(self._ref[block])

    def fits(self, tokens: int) -> bool:
        """Whether a sequence of ``tokens`` could be held with the pool
        otherwise idle."""
        return self.blocks_for(tokens) <= min(self.page.num_blocks - 1,
                                              self.page.max_blocks_per_seq)

    def lacking(self, tokens: int, shared: int = 0, headroom: int = 0
                ) -> int:
        """Blocks the free list is short of for admitting a sequence of
        ``tokens`` of which ``shared`` blocks are adopted, with
        ``headroom`` blocks kept back (one growth block for each slot
        already running). 0: it can be admitted."""
        return max(0, self.blocks_for(tokens) - shared + headroom
                   - len(self._free))

    def need(self, slot: int, tokens: int) -> int:
        """Blocks :meth:`ensure` would have to take for ``tokens``."""
        return max(0, self.blocks_for(tokens) - int(self._base[slot])
                   - len(self._owned[slot]))

    def ensure(self, slot: int, tokens: int) -> bool:
        """Grow ``slot``'s table to cover ``tokens`` cached tokens.
        Returns False (allocating nothing) if the pool can't cover it."""
        need = self.need(slot, tokens)
        if need <= 0:
            return True
        if need > len(self._free) or self.blocks_for(tokens) > \
                self.page.max_blocks_per_seq:
            return False
        for _ in range(need):
            b = self._free.pop()
            self._ref[b] = 1
            self.tables[slot, int(self._base[slot])
                        + len(self._owned[slot])] = b
            self._owned[slot].append(b)
        self._device_tables = None
        return True

    def drop_before(self, slot: int, first_live_token: int) -> int:
        """Give back the blocks of ``slot`` that lie wholly before
        ``first_live_token`` (a sliding window has passed them): their
        table entries become the null block, and a later :meth:`ensure`
        starts after them. Returns how many went back to the pool."""
        first = first_live_token // self.page.block_size
        freed = 0
        owned = self._owned[slot]
        while self._base[slot] < first and owned:
            b = owned.pop(0)
            self.tables[slot, int(self._base[slot])] = 0
            self._base[slot] += 1
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(b)
            freed += 1
        if not owned and self._base[slot] < first:
            self._base[slot] = first
        if freed:
            self._device_tables = None
        return freed

    def table_rows(self, slot: int):
        """What a prefill of ``slot`` is handed."""
        return self.tables[slot]

    def adopt(self, slot: int, blocks: List[int]) -> None:
        """Alias already-populated shared blocks (a cached prefix) into
        the next table positions of ``slot``. Each block's refcount is
        bumped; the slot releases them like its own, but the pool only
        reclaims a block when every reference is gone."""
        base = int(self._base[slot]) + len(self._owned[slot])
        if base + len(blocks) > self.page.max_blocks_per_seq:
            raise ValueError("adopt exceeds max_blocks_per_seq")
        for i, b in enumerate(blocks):
            self._ref[b] += 1
            self.tables[slot, base + i] = b
            self._owned[slot].append(b)
        self._device_tables = None

    def cow(self, slot: int, idx: int) -> Optional[Tuple[int, int]]:
        """Copy-on-write: swap the shared block at table position
        ``idx`` of ``slot`` for a fresh private block. Returns
        (src, dst) so the caller can device-copy the cached rows, or
        None when the pool has no free block. The shared source keeps
        its other references."""
        if not self._free:
            return None
        src = self._owned[slot][idx]
        dst = self._free.pop()
        self._ref[dst] = 1
        self._ref[src] -= 1
        self._owned[slot][idx] = dst
        self.tables[slot, idx] = dst
        self._device_tables = None
        return src, dst

    def ref_blocks(self, blocks: List[int]) -> None:
        """External holder (the radix tree) takes a reference."""
        for b in blocks:
            self._ref[b] += 1

    def unref_blocks(self, blocks: List[int]) -> List[int]:
        """Drop external references; blocks whose last reference dropped
        go back on the free list (returned for accounting)."""
        freed: List[int] = []
        for b in blocks:
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(b)
                freed.append(b)
        return freed

    def release(self, slot: int) -> None:
        for b in reversed(self._owned[slot]):
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(b)
        self._owned[slot] = []
        self._base[slot] = 0
        self.tables[slot, :] = 0
        self._device_tables = None

    def check_invariants(self) -> None:
        """Debug/chaos-test oracle: a block is on the free list iff its
        refcount is 0; no block is freed while any table or the radix
        tree still references it."""
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate blocks on free list"
        assert 0 not in free, "null block leaked onto free list"
        for b in free:
            assert self._ref[b] == 0, f"free block {b} has refcount " \
                f"{int(self._ref[b])}"
        for b in range(1, self.page.num_blocks):
            if self._ref[b] == 0:
                assert b in free, f"refcount-0 block {b} not on free list"
        for slot, owned in enumerate(self._owned):
            for b in owned:
                assert self._ref[b] > 0, f"slot {slot} references " \
                    f"refcount-0 block {b}"

    def device_tables(self) -> jax.Array:
        """Device copy of the tables, re-uploaded only after an
        ensure/release actually changed them — steady-state decode
        (most steps) reuses the cached buffer instead of paying a
        host→device transfer per generated token. Made from a COPY
        (``asarray`` may alias the host's buffer): the engine changes the
        tables while the step that was given these still runs."""
        if self._device_tables is None:
            self._device_tables = jnp.asarray(self.tables.copy())
        return self._device_tables


class KVStateManager:
    """Several kinds of KV state behind one allocator interface: a model
    whose layers keep different state (full attention: the whole
    sequence; sliding window: the blocks the window still touches;
    latent attention: the whole sequence as ONE compressed row a token
    and layer, which every head reads) has one pool, one
    :class:`BlockAllocator` and one block table a slot for each kind. What
    a row of a kind's pool holds is the model's own affair (per-head keys
    and values, packed rows, a latent ``(c_kv, k_rope)``): the manager
    counts blocks. The engine drives it as it drives a single allocator;
    a slot is admitted, grown, trimmed, preempted and released in every
    kind together, and ``stats()["kv_pools"]`` has a row a kind.

    ``kinds``: name -> (PagedConfig, window). ``window`` None keeps the
    whole sequence; a window of w keeps the blocks that positions
    ``(tokens - 1 - w, tokens - 1]`` touch, gives the others back in
    :meth:`trim`, and needs no growth headroom (a slot never holds more
    than ``blocks_in_window`` of them).

    ``slot_state``: name -> bytes a slot, for state that is NO block of
    any pool: one row of fixed size a slot however long the sequence is
    (a state-space layer's recurrent state), which the model's programs
    index by slot, write whole at a prefill and update in place. It
    never refuses a request that has a slot and never grows; a slot
    holds it exactly while it holds blocks, from :meth:`ensure` to
    :meth:`release`, so nothing is stored for it: the allocators say
    which slots are live."""

    def __init__(self, kinds: Dict[str, Tuple[PagedConfig, Optional[int]]],
                 num_slots: int,
                 slot_state: Optional[Dict[str, int]] = None):
        self.kinds = {name: BlockAllocator(page, num_slots)
                      for name, (page, _) in kinds.items()}
        self.windows = {name: w for name, (_, w) in kinds.items()}
        self.slot_state = dict(slot_state or {})
        self.num_slots = num_slots

    def _first_live(self, name: str, tokens: int) -> int:
        w = self.windows[name]
        return 0 if w is None else max(0, tokens - w)

    def _bounded(self, name: str, tokens: int) -> int:
        """Blocks of this kind a sequence of ``tokens`` holds."""
        a = self.kinds[name]
        return (a.blocks_for(tokens)
                - self._first_live(name, tokens) // a.page.block_size)

    def fits(self, tokens: int) -> bool:
        return all(
            a.blocks_for(tokens) <= a.page.max_blocks_per_seq
            and self._bounded(n, tokens) <= a.page.num_blocks - 1
            for n, a in self.kinds.items())

    def lacking(self, tokens: int, shared: int = 0, headroom: int = 0
                ) -> int:
        short = 0
        for n, a in self.kinds.items():
            grows = self.windows[n] is None
            short = max(short, self._bounded(n, tokens)
                        + (headroom if grows else 0) - a.free_blocks())
        return max(0, short)

    def free_blocks(self) -> int:
        return min(a.free_blocks() for a in self.kinds.values())

    def ensure(self, slot: int, tokens: int) -> bool:
        """Every kind covers ``tokens`` for ``slot``, or none changes. A
        windowed kind starts at the first block its window touches."""
        for n, a in self.kinds.items():
            a.drop_before(slot, self._first_live(n, tokens))
        if any(a.need(slot, tokens) > a.free_blocks()
               or a.blocks_for(tokens) > a.page.max_blocks_per_seq
               for a in self.kinds.values()):
            return False
        for a in self.kinds.values():
            a.ensure(slot, tokens)
        return True

    def trim(self, slot: int, tokens: int) -> int:
        """Give back what the windows have passed once ``slot`` caches
        ``tokens`` tokens. Returns the blocks freed."""
        freed = 0
        for n, a in self.kinds.items():
            w = self.windows[n]
            # called for every running slot on every engine turn: look at
            # the table only when the window has passed a block's end
            if w is not None and \
                    (tokens - w) // a.page.block_size > a._base[slot]:
                freed += a.drop_before(slot, tokens - w)
        return freed

    def release(self, slot: int) -> None:
        for a in self.kinds.values():
            a.release(slot)

    def check_invariants(self) -> None:
        for a in self.kinds.values():
            a.check_invariants()

    def table_rows(self, slot: int):
        return {n: a.tables[slot] for n, a in self.kinds.items()}

    def device_tables(self):
        return {n: a.device_tables() for n, a in self.kinds.items()}

    def pools(self, slot_lengths=()) -> Dict[str, dict]:
        """Blocks of each kind, for ``stats()``, and the tokens a decode
        step reads there when the running slots cache ``slot_lengths``;
        and a row for each kind of slot state: the slots that hold it."""
        out = {n: {"blocks_total": a.page.num_blocks - 1,
                   "blocks_free": a.free_blocks(),
                   "block_size": a.page.block_size,
                   "live_tokens": sum(
                       t - self._first_live(n, t) for t in slot_lengths)}
               for n, a in self.kinds.items()}
        if self.slot_state:
            live = sum(any(a._owned[slot] or a._base[slot]
                           for a in self.kinds.values())
                       for slot in range(self.num_slots))
            for n, nbytes in self.slot_state.items():
                out[n] = {"slots_total": self.num_slots, "slots_live": live,
                          "bytes_per_slot": nbytes}
        return out


# ---------------------------------------------------------------------
# The cache of a model that mixes full and sliding-window layers: two
# kinds of state (``HYBRID_KINDS``) under one :class:`KVStateManager`,
# each kind ONE pair of lane-dense pools ``k`` / ``v`` (layers of the
# kind, blocks, block size, row width) that every layer of its kind
# updates in place. What a row holds (how many KV heads, how wide a key
# and a value, packed or not) is the model's; the geometry, the
# manager, the writes and the decode kernel's work lists are here, for
# every such model (``mimo_v2.py``, ``laguna.py``) to call.

HYBRID_KINDS = ("full", "window")


def hybrid_pages(window: int, *, num_slots: int, max_seq: int,
                 block_size: int, pool_tokens: int
                 ) -> Dict[str, PagedConfig]:
    """Pool geometry of each kind: the full pool holds ``pool_tokens``;
    the window pool as many blocks a slot as a window can touch, which a
    slot never exceeds."""
    from ray_tpu.ops.pallas.paged_hybrid_decode_attention import (
        blocks_in_window)

    return {
        "full": PagedConfig(num_blocks=1 + -(-pool_tokens // block_size),
                            block_size=block_size, max_seq=max_seq),
        "window": PagedConfig(
            num_blocks=1 + num_slots * blocks_in_window(window, block_size),
            block_size=block_size, max_seq=max_seq)}


def init_hybrid_cache(page: Dict[str, PagedConfig], num_slots: int,
                      rows: Dict[str, Tuple[int, int, int]],
                      n_counters: int, dtype):
    """``rows``: kind -> (layers of the kind, key row width, value row
    width). ``counters`` is what a program reports of its step beside
    the logits (the expert layer's, summed over the routed layers)."""
    cache = {"length": jnp.zeros((num_slots,), jnp.int32),
             "counters": jnp.zeros((n_counters,), jnp.float32)}
    for kind, p in page.items():
        L, wk, wv = rows[kind]
        cache[kind] = {
            "k": jnp.zeros((L, p.num_blocks, p.block_size, wk), dtype),
            "v": jnp.zeros((L, p.num_blocks, p.block_size, wv), dtype)}
    return cache


def make_hybrid_manager(page: Dict[str, PagedConfig], window: int,
                        num_slots: int) -> KVStateManager:
    return KVStateManager({"full": (page["full"], None),
                           "window": (page["window"], window)}, num_slots)


def hybrid_pools(cache):
    """{kind: (k pool, v pool)} of a cache, as a program carries them."""
    return {kind: (cache[kind]["k"], cache[kind]["v"])
            for kind in HYBRID_KINDS}


def hybrid_cache(pools, length, counters):
    """The cache a program returns: :func:`hybrid_pools` undone."""
    new = {kind: {"k": pools[kind][0], "v": pools[kind][1]}
           for kind in HYBRID_KINDS}
    new["length"], new["counters"] = length, counters
    return new


@part("kv_store")
def fold_heads(rows):
    """(..., KV, D) keys or values as a pool row holds them, a token's KV
    heads side by side: (..., KV * D)."""
    return rows.reshape(*rows.shape[:-2], -1)


@part("kv_store")
def store_kv_rows(pool, where, k_rows, v_rows):
    """Write key rows and value rows into one kind's (k, v) pools at
    ``where`` (layer, blocks[, offsets]): in place, inside a jitted
    program whose donated pools these are."""
    kc, vc = pool
    return (kc.at[where].set(k_rows.astype(kc.dtype)),
            vc.at[where].set(v_rows.astype(vc.dtype)))


@part("kv_store")
def hybrid_decode_rows(tables, lengths, active, block_size: int):
    """Where a decode step's new row of each slot goes and what the slot
    attends: ({kind: block (B,)}, offset (B,), att_len (B,)). A slot that
    is not running writes to the null block and attends nothing,
    whatever stale length it keeps."""
    rows = jnp.arange(lengths.shape[0])
    blk = {kind: jnp.where(active, tables[kind][rows, lengths // block_size],
                           0) for kind in HYBRID_KINDS}
    return blk, lengths % block_size, jnp.where(active, lengths + 1, 0)


@part("kv_store")
def hybrid_prefill_blocks(table_rows, true_len, nblk: int, block_size: int):
    """{kind: (nblk,)} the blocks a padded prompt's rows go to: what the
    kind's table names, the null block past the prompt's end (and, for a
    window layer, behind the window, where the table no longer holds
    one)."""
    return {kind: jnp.where(jnp.arange(nblk) * block_size < true_len,
                            table_rows[kind][:nblk], 0)
            for kind in HYBRID_KINDS}


@part("kv_store")
def hybrid_decode_work(att_len, page: Dict[str, PagedConfig], window: int):
    """The hybrid decode kernel's work list of each kind for a decode
    step's lengths, built before the layer loop so that a kind's layers
    share it (None off the TPU, where the oracle attends)."""
    from ray_tpu.ops.pallas.paged_hybrid_decode_attention import (
        paged_hybrid_decode_work)

    return {kind: paged_hybrid_decode_work(
        att_len, page[kind].block_size, page[kind].max_blocks_per_seq,
        window if kind == "window" else None) for kind in HYBRID_KINDS}


def bind_hybrid_prefill(prefill, params: Params, block_size: int):
    """``call(cache, table_rows {kind: (MBS,)}, tokens (1, P), true_len,
    slot)`` around a prefill jitted per padded length (``params`` first,
    ``pad_len`` static): P must be a multiple of the block size."""

    def call(cache, table_rows, tokens, true_len, slot):
        pad_len = tokens.shape[1]
        if pad_len % block_size:
            raise ValueError(f"padded prompt {pad_len} not a multiple of "
                             f"block_size {block_size}")
        rows = {kind: jnp.asarray(table_rows[kind], jnp.int32)
                for kind in HYBRID_KINDS}
        return prefill(params, cache, rows, tokens,
                       jnp.asarray(true_len, jnp.int32),
                       jnp.asarray(slot, jnp.int32), pad_len=pad_len)

    call.jitted = prefill
    return call


@part("kv_store")
def _decode_work(lengths, page: PagedConfig):
    """The kernel's work list for a decode step's lengths, built before
    the layer scan so that every layer shares it (None off the TPU, where
    the oracle attends)."""
    return paged_decode_work(lengths, page.block_size,
                             page.max_blocks_per_seq)


def _scan_layers(attend, x, params: Params, cache: PagedCache,
                 c: LlamaConfig, positions):
    """:func:`dense_block` over the layers with the whole pool in the
    scan's CARRY, as ``attend``'s ``state = (k_pool, v_pool, l)`` in and
    ``(k_pool, v_pool)`` out. A scan cannot alias an ``xs`` input to a
    ``ys`` output, so a pool threaded through those is sliced out, copied
    and written back layer by layer into a second pool; a carry that
    ``attend`` only updates with ``.at[l, ...].set`` is updated in place,
    and the donated argument becomes the result. -> (x, k_pool, v_pool)."""
    cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)

    def body(carry, scanned):
        x, kc, vc = carry                      # pools (L, NB, bs, KV * D)
        layer, l = scanned
        x, (kc, vc) = dense_block(x, layer, c, cos, sin, positions, attend,
                                  (kc, vc, l))
        return (x, kc, vc), None

    n_layers = cache["k"].shape[0]
    (x, k_pool, v_pool), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    return x, k_pool, v_pool


def make_chunked_paged_prefill(params: Params, config: LlamaConfig,
                               page: PagedConfig):
    """Chunked prefill over the paged pool (vLLM/Sarathi chunked
    prefill, paged flavor): one fixed-size chunk per call; chunk k/v
    scatter into the blocks the table row names, attention runs over the
    slot's full prefix+chunk rows gathered via the table. The pool is
    carried whole through the layer scan and updated in place
    (:func:`_scan_layers`): a call moves the chunk's rows in and one
    slot's blocks out, per layer, and nothing else of the pool.

    chunk(cache, table_row (MBS,), tokens (1, C), true_len-in-chunk,
          start_pos, slot) → (cache, last_logits)

    C must be a multiple of block_size; ``start_pos`` may be ANY
    position (the k/v scatter is row-level, not block-level), which is
    what lets a radix-prefix-cache hit resume mid-block after a
    copy-on-write of the divergence block: cached rows before
    ``start_pos`` stay untouched, new rows land at their exact
    (block, offset) targets. The block budget for the WHOLE prompt is
    ensured at admission, so chunking here only splits the compute,
    never the allocation.
    """
    c = config
    bs = page.block_size
    rows_shape = (1, page.max_blocks_per_seq * bs, c.n_kv_heads, c.head_dim)

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("pad_len",))
    def chunk(params: Params, cache: PagedCache, table_row, tokens,
              true_len, start_pos, slot, pad_len: int):
        rel = jnp.arange(pad_len)
        row_abs = start_pos + rel
        mask_valid = rel < true_len                           # (C,)
        # row-level scatter target: each chunk row lands at its exact
        # (block, offset), invalid rows in the null block; rows cached
        # before start_pos are never touched
        with part("kv_store"):
            row_blk = jnp.where(mask_valid, table_row[row_abs // bs], 0)
            row_off = row_abs % bs                            # (C,)

        def attend(q, k, v, state):
            kc, vc, l = state
            with part("kv_store"):
                kb = fold_heads(jnp.where(mask_valid[:, None, None], k[0],
                                          0.0))
                vb = fold_heads(jnp.where(mask_valid[:, None, None], v[0],
                                          0.0))
            kc, vc = store_kv_rows((kc, vc), (l, row_blk, row_off), kb, vb)
            # gather the slot's full row set (prefix + this chunk) and
            # attend with absolute-position causal visibility
            with part("attention"):
                out = attend_rows(q, kc[l, table_row].reshape(rows_shape),
                                  vc[l, table_row].reshape(rows_shape),
                                  row_abs[None, :], c.head_dim ** -0.5)
            return out, (kc, vc)

        x = embed(params, tokens, c)                          # (1, C, E)
        x, new_k, new_v = _scan_layers(attend, x, params, cache, c,
                                       row_abs[None, :])
        logits = logits_f32(x, params, c,
                            row=(0, jnp.maximum(true_len - 1, 0)))
        new_len = cache["length"].at[slot].set(start_pos + true_len)
        return ({"k": new_k, "v": new_v, "length": new_len}, logits)

    return _bind_padded(chunk, params, tokens_at=1, multiple_of=bs)


def make_paged_decode_step(params: Params, config: LlamaConfig,
                           page: PagedConfig):
    """step(cache, tables (B,MBS) i32, tokens (B,) i32, active (B,) bool)
    → (cache, logits (B, vocab) f32). Each active slot's table must
    already cover position ``length`` (the engine allocates between
    steps); inactive slots write into the null block.

    The kernel is told which slots run: an inactive slot attends with
    length 0 whatever ``cache["length"]`` holds for it (nothing resets
    that when a slot is released, and a slot in a chunked prefill has one
    too), so it costs the kernel no step, and its attention output is
    zeros. Its row of the logits means nothing; the engine reads the rows
    of active slots only (``LLMEngine._loop_once``).

    The pool is carried whole through the layer scan and updated in
    place (:func:`_scan_layers`): per layer a step writes one row for
    each slot and the kernel reads the blocks the tables name, out of
    the same buffer the caller donated and gets back."""
    c = config
    bs = page.block_size

    def step(params: Params, cache: PagedCache, tables, tokens, active):
        lengths = cache["length"]
        slot_rows = jnp.arange(tokens.shape[0])
        with part("kv_store"):
            # physical write target of the new token per slot
            blk = tables[slot_rows, lengths // bs]                 # (B,)
            blk = jnp.where(active, blk, 0)                        # null
            off = lengths % bs
            att_len = jnp.where(active, lengths + 1, 0)
        work = _decode_work(att_len, page)

        def attend(q, k, v, state):
            kc, vc, l = state
            kc, vc = store_kv_rows((kc, vc), (l, blk, off),
                                   fold_heads(k[:, 0]), fold_heads(v[:, 0]))
            # the pool goes as the layer scan carries it, whole: a
            # per-layer slice here would be a copy of that layer
            out = paged_decode(q, kc, vc, l, tables, att_len,
                               scale=c.head_dim ** -0.5, work=work)
            return out, (kc, vc)

        x = embed(params, tokens, c)[:, None, :]                  # (B,1,E)
        x, new_k, new_v = _scan_layers(attend, x, params, cache, c,
                                       lengths[:, None])
        logits = logits_f32(x, params, c, row=(slice(None), 0))
        new_len = jnp.where(active, lengths + 1, lengths)
        return ({"k": new_k, "v": new_v, "length": new_len}, logits)

    return _bind_params(jax.jit(step, donate_argnums=(1,)), params)


def make_paged_prefill(params: Params, config: LlamaConfig,
                       page: PagedConfig):
    """prefill(cache, table_row (MBS,) i32, tokens (1,P) padded, true_len,
    slot) → (cache, last_logits (vocab,) f32). P must be a multiple of
    block_size (jitted per bucketed P); prompt KV lands in the blocks the
    table row names, padding rows in the null block. The pool is carried
    whole through the layer scan and updated in place
    (:func:`_scan_layers`): a prefill moves the prompt's blocks into the
    pool and nothing else of it."""
    c = config
    bs = page.block_size

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("pad_len",))
    def prefill(params: Params, cache: PagedCache, table_row, tokens,
                true_len, slot, pad_len: int):
        nblk = pad_len // bs
        blocks_shape = (nblk, bs, c.n_kv_heads * c.head_dim)
        positions = jnp.arange(pad_len)[None, :]
        mask_valid = positions[0] < true_len                  # (P,)
        # rows past true_len write into the null block
        with part("kv_store"):
            dest = jnp.where(jnp.arange(nblk) * bs < true_len,
                             table_row[:nblk], 0)              # (nblk,)

        def attend(q, k, v, state):
            # causal within the prompt; its k/v fill whole blocks
            kc, vc, l = state
            with part("kv_store"):
                kb = jnp.where(mask_valid[:, None, None], k[0],
                               0.0).reshape(blocks_shape)
                vb = jnp.where(mask_valid[:, None, None], v[0],
                               0.0).reshape(blocks_shape)
            pools = store_kv_rows((kc, vc), (l, dest), kb, vb)
            return mha_reference(q, k, v, causal=True), pools

        x = embed(params, tokens, c)                          # (1, P, E)
        x, new_k, new_v = _scan_layers(attend, x, params, cache, c,
                                       positions)
        logits = logits_f32(x, params, c,
                            row=(0, jnp.maximum(true_len - 1, 0)))
        new_len = cache["length"].at[slot].set(true_len)
        return ({"k": new_k, "v": new_v, "length": new_len}, logits)

    return _bind_padded(prefill, params, tokens_at=1, multiple_of=bs)


def make_paged_inject(config: LlamaConfig, page: PagedConfig):
    """inject(cache, table_row (MBS,) i32, k, v, true_len, slot) → cache.
    k/v are (layers, P, KV, D), or (layers, P, KV * D) as
    :func:`extract_kv` gives them, with P a multiple of block_size; rows
    at or beyond true_len must be zero. The KV-transfer half of PD
    disaggregation and the prefix cache, over blocks."""
    c = config
    bs = page.block_size

    @functools.partial(jax.jit, donate_argnums=(0,),
                       static_argnames=("pad_len",))
    def inject(cache: PagedCache, table_row, k, v, true_len, slot,
               pad_len: int):
        nblk = pad_len // bs
        dest = jnp.where(jnp.arange(nblk) * bs < true_len,
                         table_row[:nblk], 0)
        kb = k.reshape(c.n_layers, nblk, bs, -1)
        vb = v.reshape(c.n_layers, nblk, bs, -1)
        kc = cache["k"].at[:, dest].set(kb.astype(cache["k"].dtype))
        vc = cache["v"].at[:, dest].set(vb.astype(cache["v"].dtype))
        new_len = cache["length"].at[slot].set(true_len)
        return {"k": kc, "v": vc, "length": new_len}

    def call(cache, table_row, k, v, true_len, slot):
        pad_len = k.shape[1]
        if pad_len % bs:
            raise ValueError(f"padded KV length {pad_len} not a multiple "
                             f"of block_size {bs}")
        return inject(cache, jnp.asarray(table_row, jnp.int32),
                      jnp.asarray(k), jnp.asarray(v),
                      jnp.asarray(true_len, jnp.int32),
                      jnp.asarray(slot, jnp.int32), pad_len=pad_len)

    return call


def make_block_copy(config: LlamaConfig, page: PagedConfig):
    """copy(cache, src_block, dst_block) → cache. Device-side copy of
    one pool block's k/v rows across all layers: the copy-on-write
    primitive behind radix prefix sharing — a slot that must write into
    a shared block first duplicates it, so the cached original stays
    read-only for every other reference."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def copy(cache: PagedCache, src, dst):
        kc = cache["k"].at[:, dst].set(cache["k"][:, src])
        vc = cache["v"].at[:, dst].set(cache["v"][:, src])
        return {"k": kc, "v": vc, "length": cache["length"]}

    def call(cache, src: int, dst: int):
        return copy(cache, jnp.asarray(src, jnp.int32),
                    jnp.asarray(dst, jnp.int32))

    return call


def extract_kv(cache: PagedCache, allocator: BlockAllocator, slot: int,
               true_len: int) -> Tuple[np.ndarray, np.ndarray]:
    """Device→host copy of one slot's cached KV rows [0, true_len), as
    the pool holds them, (layers, true_len, KV * D) each: gathers the
    slot's blocks and trims. The PD/prefix-cache export."""
    bs = allocator.page.block_size
    nblk = allocator.blocks_for(true_len)
    ids = allocator.tables[slot, :nblk]
    k, v = jax.device_get((cache["k"][:, ids], cache["v"][:, ids]))
    L, _, _, W = k.shape
    k = k.reshape(L, nblk * bs, W)[:, :true_len]
    v = v.reshape(L, nblk * bs, W)[:, :true_len]
    return np.asarray(k), np.asarray(v)


def prompt_bucket(page: PagedConfig):
    """``pad(n)``: the padded length of a prompt of ``n`` tokens in a
    pool of ``page``'s geometry, a bucket of whole blocks, at most a
    sequence's blocks."""
    cap = page.max_blocks_per_seq * page.block_size
    return lambda n: min(pad_to_block_bucket(n, page.block_size), cap)


def pad_to_block_bucket(n: int, block_size: int,
                        buckets=(64, 128, 256, 512, 1024, 2048)) -> int:
    """Prompt padding bucket that is always a block_size multiple."""
    for b in buckets:
        if n <= b and b % block_size == 0:
            return b
    m = max(block_size, buckets[-1])
    return -(-n // m) * m
