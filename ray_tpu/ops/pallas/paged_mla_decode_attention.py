"""Paged decode attention over a LATENT cache, in the absorbed form
(Pallas TPU): one new query token per slot against that slot's paged
rows ``(c_kv, k_rope)``, which every head reads alike.

Latent (multi-head latent, "MLA") attention caches, for a token and
layer, one compressed row ``c_kv`` (``rank`` wide) and one rotated key
row ``k_rope`` shared by all heads. A head's key is ``[c_kv W_k,h ;
k_rope]`` and its value ``c_kv W_v,h``; with ``W_k,h`` folded into the
query and ``W_v,h`` applied to the output (the caller's two products),
a decode step needs only the rows themselves:

    pool     (L, NB, bs, W)   a row = [c_kv (rank) ; k_rope ; zeros] with
                              W a multiple of the 128 lanes
    q        (B, H, W)        [q_nope_h W_k,h^T (rank) ; q_rope_h ; zeros]
    z_h[j]   = scale * q_h . row_j
    u_h      = sum_j softmax(z_h)[j] * row_j[:rank]        -> (B, H, rank)

so keys and values are the SAME rows: a block is read once, all ``H``
heads multiply it in one product, and the values product takes the first
``rank`` columns of the tile that is already there. The row is padded to
whole lanes by the model (576 -> 640 at the published 512 + 64: what the
chip's tiled layout would pad a 576-wide row to anyway, stated in the
shape instead of hidden in the layout), so every slice is aligned.

The grid is the work list of :func:`decode_work_list` (live (slot,
block) pairs in slot order, ``BLOCKS_PER_STEP`` blocks a step, none for
an empty slot), ``layer``, ``tables``, ``lengths`` and the list ride as
scalar prefetch, and the online softmax is ``decode_attention.py``'s: as
in ``paged_hybrid_decode_attention.py``, whose one-product step this is
with one kv head, no window and no sink.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.ops.pallas.paged_decode_attention import decode_work_list
from ray_tpu.util.profiling import part

NEG_INF = -1e30
_LANES = 128
# Blocks of a slot in a step. On a v5e at 64 heads, rows 640 wide and block
# 64 a live block costs 0.63 / 0.35 / 0.25 / 0.19 us at 1 / 2 / 4 / 8 a step
# (its copy: 0.10): a step's fixed cost is shared and the products' tiles
# fill, while a slot's last step reads up to G - 1 blocks for nothing.
BLOCKS_PER_STEP = 8


def padded_row(width: int) -> int:
    """A latent row's width in the pool: whole lanes."""
    return -(-width // _LANES) * _LANES


def mla_work_list(lengths, block_s: int, max_blocks: int):
    """The kernel's work list for ``lengths`` (B,) (the new token
    included; 0 for a slot that is not running). It depends on the
    lengths alone: a decode step builds it once for all its layers."""
    return decode_work_list(
        lengths.astype(jnp.int32), block_s, max_blocks,
        blocks_per_step=min(BLOCKS_PER_STEP, max_blocks))


def _kernel(layer_ref, tables_ref, len_ref, slot_ref, block_ref, q_ref,
            *rest, scale: float, block_s: int, max_blocks: int, G: int,
            rank: int):
    del layer_ref, tables_ref            # used by the index maps only
    row_refs, (o_ref, acc_ref, m_ref, l_ref) = rest[:G], rest[G:]
    i = pl.program_id(0)
    ib = block_ref[i]                    # the step's first block
    length = len_ref[slot_ref[i]]

    @pl.when(ib == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # the step's G blocks as one: token rows ib * bs .. (ib + G) * bs - 1
    rows = jnp.concatenate([r[0] for r in row_refs], axis=0)   # (G*bs, W)
    s = jax.lax.dot_general(
        q_ref[0], rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale            # (H, G*bs)
    col = ib * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < length, s, NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:] = jnp.broadcast_to(
        l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
        l_ref.shape)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                    # (H, rank)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    # the slot's last step: the one its length ends in, or the table's
    @pl.when(((ib + G) * block_s >= length) | (ib + G >= max_blocks))
    def _finalize():
        o_ref[0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


def paged_mla_decode_kernel(q, pool, layer, tables, lengths, *, scale: float,
                            rank: int, work=None,
                            name: str = "paged_mla_decode",
                            interpret: bool = False):
    """q (B, H, W); pool (L, NB, bs, W); layer () int32; tables (B, MBS)
    int32; lengths (B,) int32, the new token included, 0 for a slot that
    is not running. -> (B, H, rank) in q.dtype; the row of a slot of
    length 0 is zeros. ``work`` is :func:`mla_work_list` of the same
    lengths from a caller that builds it once for many layers."""
    B, H, W = q.shape
    bs = pool.shape[2]
    MBS = tables.shape[1]
    if pool.shape[3] != W or W % _LANES or rank > W:
        raise ValueError(f"rows {pool.shape[3]} wide, queries {W}, rank "
                         f"{rank}: whole lanes, alike")
    G = min(BLOCKS_PER_STEP, MBS)
    lengths = lengths.astype(jnp.int32)
    n_work, work_slot, work_block = (
        mla_work_list(lengths, bs, MBS) if work is None else work)
    kernel = functools.partial(_kernel, scale=scale, block_s=bs,
                               max_blocks=MBS, G=G, rank=rank)

    def row_ix(g):
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

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_work,),
        in_specs=([pl.BlockSpec((1, H, W), slot_ix)]
                  + [pl.BlockSpec((None, 1, bs, W), row_ix(g))
                     for g in range(G)]),
        out_specs=pl.BlockSpec((1, H, rank), slot_ix),
        scratch_shapes=[
            pltpu.VMEM((H, rank), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
        ],
    )
    with part(name):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name=name,
        )(jnp.asarray(layer, jnp.int32).reshape(1),
          tables.astype(jnp.int32), lengths, work_slot, work_block, q,
          *[pool] * G)
        # no step visits an empty slot, so nothing wrote its row
        return jnp.where((lengths > 0)[:, None, None], out, 0)


@part("attention")
def paged_mla_attention_reference(q, pool, layer, tables, lengths, *,
                                  scale: float, rank: int):
    """XLA path (and the kernel's oracle), same arguments: gather every
    block the table names and attend over the rows up to the length."""
    B, H, W = q.shape
    S = tables.shape[1] * pool.shape[2]
    rows = pool[layer, tables].reshape(B, S, W).astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows) * scale
    ok = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(ok[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bsr->bhr", p, rows[..., :rank])
    return jnp.where((lengths > 0)[:, None, None], out, 0).astype(q.dtype)


def paged_mla_decode_work(lengths, block_s: int, max_blocks: int):
    """:func:`mla_work_list` where :func:`paged_mla_decode` runs the
    kernel; None where its oracle attends, which walks no list."""
    if not attention.on_tpu():
        return None
    return mla_work_list(lengths, block_s, max_blocks)


def paged_mla_decode(q, pool, layer, tables, lengths, *, scale: float,
                     rank: int, work=None):
    """The kernel on a TPU, its oracle elsewhere."""
    if attention.on_tpu():
        return paged_mla_decode_kernel(q, pool, layer, tables, lengths,
                                       scale=scale, rank=rank, work=work)
    return paged_mla_attention_reference(q, pool, layer, tables, lengths,
                                         scale=scale, rank=rank)
