"""Paged flash-decoding attention (Pallas TPU): one new query token per
slot against that slot's KV cache stored in non-contiguous fixed-size
blocks (a vLLM-style paged KV pool, TPU-native).

Capability bar: vLLM's paged attention, which the reference delegates to
(``python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py``).
The TPU shape of the idea: the pool is one static, LANE-DENSE
(layers, num_blocks, bs, KV * D) array for the whole model, one row a
token with its KV heads side by side; each slot's logical cache in one
layer is the sequence of that layer's pool blocks named by its
block-table row. The layer index, the block tables, the lengths and the
work list ride as SCALAR-PREFETCH operands, so the kernel's BlockSpec
index maps translate (layer, slot, logical block) → physical pool block
at grid-issue time — neither a layer's slice of the pool nor a
contiguous per-slot cache is ever materialized in HBM. The caller (the
decode step's layer scan) hands over the pool it carries, whole, and the
kernel reads only the blocks the tables name.

The grid is the WORK LIST (:func:`decode_work_list`): one step for each
run of G logical blocks of a slot that hold cached tokens, in slot
order, and none for any other. Steps, copies and arithmetic are in
proportion to ``sum(ceil(length / bs))``, not to slots x max_seq / bs: a
slot of length 0 costs nothing, and its output row is zeros. The grid's
bound is the list's length, a traced scalar.

The body is :mod:`ray_tpu.ops.pallas.paged_hybrid_decode_attention`'s,
which this file calls with a key as wide as a value, no window and no
sink: ONE kernel body serves every paged pool of whole K/V rows. GQA
there: a slot's first step lays each query row out as wide as a key row,
zeros outside its own KV head's ``D`` columns, so one product gives every
head's scores against G blocks with no mask by head, and the block is
read once and never re-laid-out head by head. What stays here is the
dense pools' interface (a query of ``D`` columns, KV heads counted from
the row's width), the work list that every paged kernel walks, and the
names the traces are read by: the custom call and its scope are
``paged_decode_attention``.

Layout contract:
    q        (B, 1, H, D)    new-token queries; row h's KV head is
                             ``h // (H // KV)`` (H may be several
                             positions' heads, KV-major)
    k_pool   (L, NB, bs, KV * D)  paged key pool, every layer
    v_pool   (L, NB, bs, KV * D)
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

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention
from ray_tpu.util.profiling import part


NEG_INF = -1e30


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


def _kv_heads(q, k_pool) -> int:
    """KV heads side by side in a pool row, each as wide as a query."""
    D = q.shape[-1]
    KV, rest = divmod(k_pool.shape[3], D)
    if rest or q.shape[2] % KV:
        raise ValueError(f"{q.shape[2]} query rows of {D} columns against "
                         f"pool rows of {k_pool.shape[3]}")
    return KV


def paged_decode_attention(q, k_pool, v_pool, layer, tables, lengths, *,
                           scale: float, interpret: bool = False,
                           work=None):
    """q (B,1,H,D); k/v_pool (L,NB,bs,KV*D); layer () int32; tables
    (B,MBS) int32; lengths (B,) int32. Returns (B, 1, H, D) in q.dtype;
    the row of a slot of length 0 is zeros.

    ``work`` is ``paged_decode_work(lengths, bs, MBS)``'s list from a
    caller that attends many layers over the same lengths and builds it
    once (XLA leaves it inside a layer scan's body); built by the kernel
    when absent."""
    # that module imports this one's ``decode_work_list``
    from ray_tpu.ops.pallas import paged_hybrid_decode_attention as hybrid

    B, _, H, D = q.shape
    # KV head j's key is the D columns of a row from j * D
    k_slices = tuple((j * D,) for j in range(_kv_heads(q, k_pool)))
    out = hybrid.paged_hybrid_decode_attention(
        q.reshape(B, H, D), k_pool, v_pool, layer, tables, lengths,
        scale=scale, k_slices=k_slices, dv=D, work=work,
        name="paged_decode_attention", interpret=interpret)
    return out.reshape(B, 1, H, D)


@part("attention")
def paged_attention_reference(q, k_pool, v_pool, layer, tables, lengths, *,
                              scale: float):
    """XLA path (and the kernel's correctness oracle), same arguments as
    the kernel: gather the per-slot cache of one layer via the block
    table, then grouped-einsum attention."""
    B, _, H, D = q.shape
    bs = k_pool.shape[2]
    KV = _kv_heads(q, k_pool)
    MBS = tables.shape[1]
    group = H // KV
    S = MBS * bs
    k = k_pool[layer, tables].reshape(B, S, KV, D)   # (B, MBS, bs, KV*D) →
    v = v_pool[layer, tables].reshape(B, S, KV, D)
    qg = q.astype(jnp.float32).reshape(B, KV, group, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


def paged_decode_work(lengths, block_s: int, max_blocks: int):
    """The kernel's work list (its blocks a step included) where
    :func:`paged_decode` runs the kernel; None where its oracle attends,
    which walks no list. Built once a decode step, before the layer
    scan, for every layer."""
    from ray_tpu.ops.pallas import paged_hybrid_decode_attention as hybrid

    return hybrid.paged_hybrid_decode_work(lengths, block_s, max_blocks)


def paged_decode(q, k_pool, v_pool, layer, tables, lengths, *, scale: float,
                 work=None):
    """The kernel on a TPU, its oracle elsewhere."""
    if attention.on_tpu():
        return paged_decode_attention(q, k_pool, v_pool, layer, tables,
                                      lengths, scale=scale, work=work)
    return paged_attention_reference(q, k_pool, v_pool, layer, tables,
                                     lengths, scale=scale)
