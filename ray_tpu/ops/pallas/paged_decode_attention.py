"""Paged flash-decoding attention (Pallas TPU): one new query token per
slot against that slot's KV cache stored in non-contiguous fixed-size
blocks (a vLLM-style paged KV pool, TPU-native).

Capability bar: vLLM's paged attention, which the reference delegates to
(``python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py``).
The TPU shape of the idea: the pool is one static
(layers, num_blocks, bs, KV, D) array for the whole model; each slot's
logical cache in one layer is the sequence of that layer's pool blocks
named by its block-table row. The layer index, the block tables, the
lengths and the work list ride as SCALAR-PREFETCH operands, so the
kernel's BlockSpec index maps translate (layer, slot, logical block) →
physical pool block at grid-issue time — neither a layer's slice of the
pool nor a contiguous per-slot cache is ever materialized in HBM. The
caller (the decode step's layer scan) hands over the pool it carries,
whole, and the kernel reads only the blocks the tables name.

The grid is the WORK LIST (:func:`decode_work_list`): one step for each
(slot, logical block) pair that holds cached tokens, in slot order, and
none for any other. Steps, copies and arithmetic are in proportion to
``sum(ceil(length / bs))``, not to slots x max_seq / bs: a slot of length
0 costs nothing, and its output row is zeros. The grid's bound is the
list's length, a traced scalar; a slot's first pair resets the
accumulators and its last pair writes the output row.

GQA: a step multiplies all the heads' queries with the block's rows of
every kv head at once and masks each head to its own kv head's rows; the
block is read once and never re-laid-out head by head.

Layout contract:
    q        (B, 1, H, D)    new-token queries
    k_pool   (L, NB, bs, KV, D)  paged key pool, every layer
    v_pool   (L, NB, bs, KV, D)
    layer    () int32        which layer of the pool to attend over
                             (scalar prefetch; may be traced)
    tables   (B, MBS) int32  physical block id per logical block; entries
                             past the valid prefix MUST name a real block
                             (conventionally the reserved null block 0):
                             no step computes on them, but the one-step
                             lookahead may name one
    lengths  (B,) int32      valid tokens per slot (incl. the new token);
                             0 for a slot that is not running

Online-softmax recurrence identical to ``decode_attention.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.util.profiling import part


NEG_INF = -1e30
_LANES = 128


def decode_work_list(lengths, block_s: int, max_blocks: int,
                     first_block=None, max_pairs=None,
                     blocks_per_step: int = 1):
    """The (slot, logical block) pairs that hold cached tokens, in slot
    order: ``{(s, j): j < ceil(lengths[s] / block_s)}``. Returns
    ``(n_work () i32, work_slot (B * max_blocks + 1,) i32, work_block
    (same))``. Entries from ``n_work`` on repeat the last pair: no step
    computes on them, but the pipeline looks one step past the last (with
    every table full too, hence the + 1; without it that run halted the
    chip) and has to find valid indices there. A few fused integer
    operations that depend on the lengths alone: a caller with many layers
    builds the list once a decode step, not once a layer.

    For a caller that reads only a slot's last blocks (a sliding window):
    ``first_block`` (B,) i32 is the first logical block of each slot's
    pairs, ``j >= first_block[s]``, and ``max_pairs`` the most pairs a
    slot can then have (it sizes the lists in place of ``max_blocks``).
    For a kernel that takes ``blocks_per_step`` = G blocks in a step: a
    slot's every G-th pair, each standing for blocks ``j .. j + G - 1``
    (the last may run past the slot's end), in lists of ``B *
    ceil(pairs a slot / G) + 1``."""
    B = lengths.shape[0]
    nblk = jnp.clip((lengths.astype(jnp.int32) + block_s - 1) // block_s,
                    0, max_blocks)
    per_slot = max_blocks
    if first_block is not None:
        nblk = jnp.clip(nblk - first_block, 0, max_pairs)
        per_slot = max_pairs
    if blocks_per_step != 1:
        nblk = (nblk + blocks_per_step - 1) // blocks_per_step
        per_slot = -(-per_slot // blocks_per_step)
    ends = jnp.cumsum(nblk)
    n_work = ends[-1]
    i = jnp.minimum(jnp.arange(B * per_slot + 1, dtype=jnp.int32),
                    jnp.maximum(n_work - 1, 0))
    # pair i belongs to the first slot whose pairs end past i
    slot = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1,
                               dtype=jnp.int32), B - 1)
    block = i - (ends - nblk)[slot]
    if blocks_per_step != 1:
        block = block * blocks_per_step
    if first_block is not None:
        block = block + first_block[slot]
    return n_work, slot, block


def _paged_kernel(layer_ref, tables_ref, len_ref, slot_ref, block_ref,
                  q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, scale: float, block_s: int, max_blocks: int,
                  num_kv: int, group: int):
    del layer_ref, tables_ref            # used by the index maps only
    i = pl.program_id(0)
    ib = block_ref[i]
    length = len_ref[slot_ref[i]]

    @pl.when(ib == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # The block is (bs * KV, D): row t * KV + j is token t's key of kv head
    # j, as the pool stores them. Every head meets every row in ONE product
    # and keeps the rows of its own kv head; the rest are masked like the
    # tokens past the length, so they add exact zeros to the sums below.
    # (Slicing a head's (bs, D) keys out of the block instead costs a
    # relayout of the whole block a head, several times the block's copy.)
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (H, bs * KV)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mine = (col % num_kv == row // group) & \
        (ib * block_s + col // num_kv < length)
    s = jnp.where(mine, s, NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:] = jnp.broadcast_to(
        l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
        l_ref.shape)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    # the slot's last pair: the block its length ends in, or the table's end
    @pl.when(((ib + 1) * block_s >= length) | (ib == max_blocks - 1))
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, layer, tables, lengths, *,
                           scale: float, interpret: bool = False,
                           work=None):
    """q (B,1,H,D); k/v_pool (L,NB,bs,KV,D); layer () int32; tables
    (B,MBS) int32; lengths (B,) int32. Returns (B, 1, H, D) in q.dtype;
    the row of a slot of length 0 is zeros.

    ``work`` is ``decode_work_list(lengths, bs, MBS)`` from a caller that
    attends many layers over the same lengths and builds the list once
    (XLA leaves it inside a layer scan's body); built here when absent."""
    B, _, H, D = q.shape
    bs, KV = k_pool.shape[2], k_pool.shape[3]
    MBS = tables.shape[1]
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    group = H // KV

    qh = q.reshape(B, H, D)
    lengths = lengths.astype(jnp.int32)
    n_work, work_slot, work_block = (
        decode_work_list(lengths, bs, MBS) if work is None else work)

    kernel = functools.partial(
        _paged_kernel, scale=scale, block_s=bs, max_blocks=MBS,
        num_kv=KV, group=group)

    def kv_ix(i, layer_ref, tables_ref, len_ref, slot_ref, block_ref):
        del len_ref
        return (layer_ref[0], tables_ref[slot_ref[i], block_ref[i]], 0, 0)

    def slot_ix(i, layer_ref, tables_ref, len_ref, slot_ref, block_ref):
        del layer_ref, tables_ref, len_ref, block_ref
        return (slot_ref[i], 0, 0)

    # the layer axis is squeezed out of the block, and a block's tokens and
    # kv heads are one axis of rows (the same bytes: no copy): the body
    # sees the (1, bs * KV, D) block of one layer
    L, NB = k_pool.shape[:2]
    k_pool = k_pool.reshape(L, NB, bs * KV, D)
    v_pool = v_pool.reshape(L, NB, bs * KV, D)
    kv_spec = pl.BlockSpec((None, 1, bs * KV, D), kv_ix)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_work,),
        in_specs=[pl.BlockSpec((1, H, D), slot_ix), kv_spec, kv_spec],
        out_specs=pl.BlockSpec((1, H, D), slot_ix),
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
        ],
    )

    with part("paged_decode_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="paged_decode_attention",
        )(jnp.asarray(layer, jnp.int32).reshape(1),
          tables.astype(jnp.int32), lengths, work_slot, work_block,
          qh, k_pool, v_pool)
        # no step visits an empty slot, so nothing wrote its row
        out = jnp.where((lengths > 0)[:, None, None], out, 0)

    return out.reshape(B, 1, H, D)


@part("attention")
def paged_attention_reference(q, k_pool, v_pool, layer, tables, lengths, *,
                              scale: float):
    """XLA path (and the kernel's correctness oracle), same arguments as
    the kernel: gather the per-slot cache of one layer via the block
    table, then grouped-einsum attention."""
    B, _, H, D = q.shape
    bs, KV = k_pool.shape[2], k_pool.shape[3]
    MBS = tables.shape[1]
    group = H // KV
    S = MBS * bs
    k = k_pool[layer, tables].reshape(B, S, KV, D)   # (B, MBS, bs, KV, D) →
    v = v_pool[layer, tables].reshape(B, S, KV, D)
    qg = q.astype(jnp.float32).reshape(B, KV, group, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


def paged_decode_work(lengths, block_s: int, max_blocks: int):
    """:func:`decode_work_list` where :func:`paged_decode` runs the
    kernel; None where its oracle attends, which walks no list. Built
    once a decode step, before the layer scan, for every layer."""
    if not attention.on_tpu():
        return None
    return decode_work_list(lengths, block_s, max_blocks)


def paged_decode(q, k_pool, v_pool, layer, tables, lengths, *, scale: float,
                 work=None):
    """The kernel on a TPU, its oracle elsewhere."""
    if attention.on_tpu():
        return paged_decode_attention(q, k_pool, v_pool, layer, tables,
                                      lengths, scale=scale, work=work)
    return paged_attention_reference(q, k_pool, v_pool, layer, tables,
                                     lengths, scale=scale)
