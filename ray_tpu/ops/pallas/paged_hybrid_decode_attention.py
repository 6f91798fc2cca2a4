"""Paged decode attention over lane-dense pools (Pallas TPU): one new
query token per slot against that slot's paged KV. The ONE body of every
paged pool of whole K/V rows: a model that mixes full and sliding-window
layers, where a key is not as wide as a value, a window layer reads only
the blocks its window still touches, and a learned per-head sink logit
may sit in the softmax's denominator; and the dense pools
(``paged_decode_attention.py``, which calls this kernel with a key as
wide as a value, no window and no sink, under its own name). The pools
are lane-dense, one row a token:

    k_pool   (L, NB, bs, Wk)       every kv head's key, packed by the
                                   model in chunks of ``c`` columns
    v_pool   (L, NB, bs, KV * Dv)
    q        (B, H, n * c)         packed like a key: kv head j's key is
                                   the columns ``k_slices[j]`` (n starts,
                                   each c wide) of a row, in q's order;
                                   row h's kv head is ``h // (H // KV)``

so a 192-wide key needs no padding to 256 lanes: the model packs it into
whole chunks (``ray_tpu.models.mimo_v2.pack_keys``) and every slice the
kernel takes is aligned. ``layer``, ``tables``, ``lengths`` and the work
list ride as scalar prefetch.

The grid is the WORK LIST (:func:`hybrid_work_list`:
``paged_decode_attention.decode_work_list`` with a first block and G
blocks a step): one step for each run of G logical blocks of a slot that
the call must read, in slot order, and none for any other; its bound is
the list's length, a traced scalar. A full layer's blocks are a slot's ``0 .. ceil(length /
bs) - 1``, G = 4 of them a step; a window layer's start at the block
that holds position ``length - window`` and are at most
``blocks_in_window``, all in ONE step: blocks wholly behind the window
are never named, so they are never read, and the allocator may have
freed them (their table entries are then the null block). A slot of
length 0 has no step and costs nothing; its output row is zeros. The
pool is passed G times, with an index map for each block of a step; one
past the slot's last block names the last again, and its keys are masked
like any past the length.

A step is ONE scores product and ONE values product for all heads and
all G blocks. A slot's first step lays each head's packed query out as
wide as a key row (its chunks at its own kv head's, exact zeros
elsewhere), so ``q' (H, Wk) . K^T (Wk, G * bs)`` is every head's scores
with no slice of a block and no mask by head; one online-softmax update
(as in ``decode_attention.py``) runs on the ``(H, G * bs)`` tile; ``p (H,
G * bs) . V (G * bs, KV * Dv)`` goes into an ``(H, KV * Dv)`` float32
accumulator, of which a head keeps its own kv head's ``Dv`` columns
once, at the slot's last step. There the sink joins the denominator too:
``l += exp(sink - m)``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.ops.pallas.paged_decode_attention import decode_work_list
from ray_tpu.util.profiling import part

NEG_INF = -1e30
_LANES = 128
# Blocks of a full layer in a step. On a v5e at 64 heads, 4 kv heads, keys
# 192 and block 64 a block costs 0.65 / 0.44 / 0.39 / 0.35 / 0.33 / 0.34 us
# at 1 / 2 / 3 / 4 / 6 / 8 a step (its copy: 0.20): a step's fixed cost is
# shared, the products fill the MXU's tiles, and a slot's last step reads
# up to G - 1 blocks for nothing.
_FULL_BLOCKS_PER_STEP = 4


def blocks_in_window(window: int, block_size: int) -> int:
    """Blocks that the keys ``(p - window, p]`` can touch, for any p."""
    return (window - 2) // block_size + 2


def _first_block(length, window, block_s):
    if window is None:
        return 0
    return jnp.maximum(length - window, 0) // block_s


def _blocks_visited(window, block_s: int, max_blocks: int) -> int:
    """The most blocks of a slot that a call reads."""
    if window is None:
        return max_blocks
    return min(blocks_in_window(window, block_s), max_blocks)


def blocks_per_step(window, block_s: int, max_blocks: int) -> int:
    """G: a window layer's blocks all in one step, a full layer's by
    :data:`_FULL_BLOCKS_PER_STEP`."""
    n_visit = _blocks_visited(window, block_s, max_blocks)
    return n_visit if window is not None else min(_FULL_BLOCKS_PER_STEP,
                                                  n_visit)


def hybrid_work_list(lengths, block_s: int, max_blocks: int,
                     window: Optional[int] = None):
    """The work list of a call over ``lengths`` (B,) (the new token
    included; 0 for a slot that is not running): :func:`decode_work_list`
    from each slot's first block in the window, in this kernel's blocks a
    step. It depends on the lengths alone, so a decode step builds one
    for its full layers and one for its window layers."""
    lengths = lengths.astype(jnp.int32)
    return decode_work_list(
        lengths, block_s, max_blocks,
        first_block=(None if window is None
                     else _first_block(lengths, window, block_s)),
        max_pairs=_blocks_visited(window, block_s, max_blocks),
        blocks_per_step=blocks_per_step(window, block_s, max_blocks))


def _kernel(layer_ref, tables_ref, len_ref, slot_ref, block_ref, q_ref,
            *rest, scale: float, block_s: int, max_blocks: int, G: int,
            group: int, dv: int, chunk: int, k_slices,
            window: Optional[int], has_sink: bool):
    del layer_ref, tables_ref            # used by the index maps only
    k_refs, v_refs, rest = rest[:G], rest[G:2 * G], rest[2 * G:]
    if has_sink:
        sink_ref, o_ref, qw_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, qw_ref, acc_ref, m_ref, l_ref = rest
    i = pl.program_id(0)
    ib = block_ref[i]                    # the step's first block
    length = len_ref[slot_ref[i]]
    first = _first_block(length, window, block_s)
    H = q_ref.shape[1]

    def kv_of_row(width):                # each query row's kv head
        return jax.lax.broadcasted_iota(jnp.int32, (H, width), 0) // group

    @pl.when(ib == first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        # The query as wide as a key row: chunk t of a head's packed
        # query at the start its kv head's key has it, zeros in every
        # other chunk. Whole (H, chunk) tiles, selected by row.
        for st in range(0, qw_ref.shape[1], chunk):
            piece = jnp.zeros((H, chunk), qw_ref.dtype)
            for j, starts in enumerate(k_slices):
                for t, s_jt in enumerate(starts):
                    if s_jt == st:
                        piece = jnp.where(
                            kv_of_row(chunk) == j,
                            q_ref[0, :, t * chunk:(t + 1) * chunk], piece)
            qw_ref[:, st:st + chunk] = piece

    # the step's G blocks as one: token rows ib * bs .. (ib + G) * bs - 1
    k_rows = jnp.concatenate([r[0] for r in k_refs], axis=0)
    v_rows = jnp.concatenate([r[0] for r in v_refs], axis=0)
    # Every head's scores in one product: the zeros of the wide query
    # add nothing, so no head is masked and no key is sliced out.
    s = jax.lax.dot_general(
        qw_ref[:], k_rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (H, G * bs)
    col = ib * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = col < length
    if window is not None:
        ok &= col >= length - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:] = jnp.broadcast_to(
        l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
        l_ref.shape)
    # every kv head's values at once: (H, KV * dv), a head's own slice
    # is taken at the end
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(v_rows.dtype), v_rows, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    # the slot's last step: the one its length ends in, or the last the
    # table or the window allows
    n_visit = _blocks_visited(window, block_s, max_blocks)

    @pl.when(((ib + G) * block_s >= length)
             | (ib + G >= jnp.minimum(first + n_visit, max_blocks)))
    def _finalize():
        l = l_ref[:, :1]
        if has_sink:
            l = l + jnp.exp(sink_ref[:, :1] - m_ref[:, :1])
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = jnp.zeros((H, dv), jnp.float32)
        for j in range(len(k_slices)):
            out = jnp.where(kv_of_row(dv) == j,
                            acc_ref[:, j * dv:(j + 1) * dv], out)
        o_ref[0] = (out / l_safe).astype(o_ref.dtype)


def paged_hybrid_decode_attention(
        q, k_pool, v_pool, layer, tables, lengths, *, scale: float,
        k_slices: Sequence[Tuple[int, ...]], dv: int,
        window: Optional[int] = None, sink=None, work=None,
        name: Optional[str] = None, interpret: bool = False):
    """q (B, H, n*c) packed; k_pool (L, NB, bs, Wk); v_pool
    (L, NB, bs, KV*dv); layer () int32; tables (B, MBS) int32; lengths
    (B,) int32, the new token included, 0 for a slot that is not running;
    ``sink`` (H,) float32 or None. -> (B, H, dv) in q.dtype; the row of a
    slot of length 0 is zeros. ``name`` is the custom call's instruction
    name, so a trace tells a full layer's calls from a window layer's
    (None: ``paged_hybrid_decode_window`` with a window, ``_full``
    without).

    ``work`` is ``hybrid_work_list(lengths, bs, MBS, window)`` from a
    caller that attends many layers over the same lengths and builds the
    list once; built here when absent."""
    if name is None:
        name = ("paged_hybrid_decode_full" if window is None
                else "paged_hybrid_decode_window")
    B, H, qw = q.shape
    bs, Wk = k_pool.shape[2], k_pool.shape[3]
    MBS = tables.shape[1]
    KV = len(k_slices)
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    chunk = qw // len(k_slices[0])
    G = blocks_per_step(window, bs, MBS)
    lengths = lengths.astype(jnp.int32)
    n_work, work_slot, work_block = (
        hybrid_work_list(lengths, bs, MBS, window) if work is None else work)
    kernel = functools.partial(
        _kernel, scale=scale, block_s=bs, max_blocks=MBS, G=G,
        group=H // KV, dv=dv, chunk=chunk,
        k_slices=tuple(map(tuple, k_slices)), window=window,
        has_sink=sink is not None)

    def kv_ix(g):
        """Block g of a step; past the slot's last block, that one."""
        def ix(i, layer_ref, tables_ref, len_ref, slot_ref, block_ref):
            slot = slot_ref[i]
            last = jnp.minimum(jnp.maximum(len_ref[slot] - 1, 0) // bs,
                               MBS - 1)
            blk = jnp.minimum(block_ref[i] + g, last)
            return (layer_ref[0], tables_ref[slot, blk], 0, 0)
        return ix

    def slot_ix(i, layer_ref, tables_ref, len_ref, slot_ref, block_ref):
        return (slot_ref[i], 0, 0)

    in_specs = ([pl.BlockSpec((1, H, qw), slot_ix)]
                + [pl.BlockSpec((None, 1, bs, Wk), kv_ix(g))
                   for g in range(G)]
                + [pl.BlockSpec((None, 1, bs, v_pool.shape[3]), kv_ix(g))
                   for g in range(G)])
    args = [q] + [k_pool] * G + [v_pool] * G
    if sink is not None:
        in_specs.append(pl.BlockSpec((H, _LANES), lambda i, *_: (0, 0)))
        args.append(jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None], (H, _LANES)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_work,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, dv), slot_ix),
        scratch_shapes=[
            pltpu.VMEM((H, Wk), q.dtype),
            pltpu.VMEM((H, KV * dv), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
        ],
    )
    with part(name):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name=name,
        )(jnp.asarray(layer, jnp.int32).reshape(1),
          tables.astype(jnp.int32), lengths, work_slot, work_block, *args)
        # no step visits an empty slot, so nothing wrote its row
        return jnp.where((lengths > 0)[:, None, None], out, 0)


@part("attention")
def paged_hybrid_attention_reference(
        q, k_pool, v_pool, layer, tables, lengths, *, scale: float,
        k_slices: Sequence[Tuple[int, ...]], dv: int,
        window: Optional[int] = None, sink=None):
    """XLA path (and the kernel's oracle), same arguments: gather every
    logical block the table names (a freed one is the null block, and is
    masked like any key outside the window), unpack the keys by
    ``k_slices`` and attend."""
    B, H, qw = q.shape
    bs = k_pool.shape[2]
    KV = len(k_slices)
    group = H // KV
    chunk = qw // len(k_slices[0])
    S = tables.shape[1] * bs
    rows_k = k_pool[layer, tables].reshape(B, S, -1).astype(jnp.float32)
    rows_v = v_pool[layer, tables].reshape(B, S, KV, dv).astype(jnp.float32)
    k = jnp.stack([jnp.concatenate(
        [rows_k[..., st:st + chunk] for st in starts], axis=-1)
        for starts in k_slices], axis=2)                    # (B,S,KV,n*c)
    qg = q.astype(jnp.float32).reshape(B, KV, group, qw)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k) * scale
    col = jnp.arange(S)[None, :]
    ok = col < lengths[:, None]
    if window is not None:
        ok &= col >= lengths[:, None] - window
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    sk = None
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(1, KV, group, 1)
        m = jnp.maximum(m, sk)
    p = jnp.exp(s - m)
    den = jnp.sum(p, axis=-1, keepdims=True)
    if sk is not None:
        den = den + jnp.exp(sk - m)
    out = jnp.einsum("bkgs,bskd->bkgd", p / den, rows_v)
    return out.reshape(B, H, dv).astype(q.dtype)


def paged_hybrid_decode_work(lengths, block_s: int, max_blocks: int,
                             window: Optional[int] = None):
    """:func:`hybrid_work_list` where :func:`paged_hybrid_decode` runs
    the kernel; None where its oracle attends, which walks no list. Built
    once a decode step for all the layers of a kind."""
    if not attention.on_tpu():
        return None
    return hybrid_work_list(lengths, block_s, max_blocks, window)


def paged_hybrid_decode(q, k_pool, v_pool, layer, tables, lengths, *,
                        work=None, name: Optional[str] = None, **kw):
    """The kernel on a TPU, its oracle elsewhere; ``kw`` is what both
    take (``scale``, ``k_slices``, ``dv``, ``window``, ``sink``)."""
    if attention.on_tpu():
        return paged_hybrid_decode_attention(
            q, k_pool, v_pool, layer, tables, lengths, work=work, name=name,
            **kw)
    return paged_hybrid_attention_reference(q, k_pool, v_pool, layer, tables,
                                            lengths, **kw)
