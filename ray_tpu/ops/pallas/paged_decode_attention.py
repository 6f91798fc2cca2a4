"""Paged flash-decoding attention (Pallas TPU): one new query token per
slot against that slot's KV cache stored in non-contiguous fixed-size
blocks (a vLLM-style paged KV pool, TPU-native).

Capability bar: vLLM's paged attention, which the reference delegates to
(``python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py``).
The TPU shape of the idea: the pool is one static
(layers, num_blocks, bs, KV, D) array for the whole model; each slot's
logical cache in one layer is the sequence of that layer's pool blocks
named by its block-table row. The layer index, the block tables and the
lengths ride as SCALAR-PREFETCH operands, so the kernel's BlockSpec index
maps translate (layer, slot, logical block) → physical pool block at
grid-issue time — neither a layer's slice of the pool nor a contiguous
per-slot cache is ever materialized in HBM. The caller (the decode step's
layer scan) hands over the pool it carries, whole, and the kernel reads
only the blocks the tables name.

GQA is an unrolled static loop over kv heads inside each program (same
rationale as ``decode_attention.py``: the KV axis is too small/unaligned
to be a grid dimension, and looping in-program reads each cache block
exactly once).

Layout contract:
    q        (B, 1, H, D)    new-token queries
    k_pool   (L, NB, bs, KV, D)  paged key pool, every layer
    v_pool   (L, NB, bs, KV, D)
    layer    () int32        which layer of the pool to attend over
                             (scalar prefetch; may be traced)
    tables   (B, MBS) int32  physical block id per logical block; entries
                             past the valid prefix MUST name a real block
                             (conventionally the reserved null block 0) —
                             they are masked out, but are still prefetched
    lengths  (B,) int32      valid tokens per slot (incl. the new token)

Online-softmax recurrence identical to ``decode_attention.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
_LANES = 128


def _paged_kernel(layer_ref, tables_ref, len_ref, q_ref, k_ref, v_ref,
                  o_ref, acc_ref, m_ref, l_ref,
                  *, scale: float, block_s: int, num_blocks: int,
                  num_kv: int, group: int):
    del layer_ref                        # used by the index maps only
    b = pl.program_id(0)
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    @pl.when(ib * block_s < length)
    def _compute():
        for j in range(num_kv):          # static unroll over kv heads
            lo, hi = j * group, (j + 1) * group
            q = q_ref[0, lo:hi, :]       # (group, D)
            k = k_ref[0, :, j, :]        # (bs, D)
            v = v_ref[0, :, j, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (group, bs)
            col = ib * block_s + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(col < length, s, NEG_INF)

            m_prev = m_ref[lo:hi, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[lo:hi, :] = jnp.broadcast_to(
                l_ref[lo:hi, :1] * alpha + jnp.sum(p, axis=1,
                                                   keepdims=True),
                (group, _LANES))
            acc_ref[lo:hi, :] = acc_ref[lo:hi, :] * alpha + \
                jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            m_ref[lo:hi, :] = jnp.broadcast_to(m_new, (group, _LANES))

    @pl.when(ib == num_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, layer, tables, lengths, *,
                           scale: float, interpret: bool = False):
    """q (B,1,H,D); k/v_pool (L,NB,bs,KV,D); layer () int32; tables
    (B,MBS) int32; lengths (B,) int32. Returns (B, 1, H, D) in q.dtype."""
    B, _, H, D = q.shape
    bs, KV = k_pool.shape[2], k_pool.shape[3]
    MBS = tables.shape[1]
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    group = H // KV

    qh = q.reshape(B, H, D)

    kernel = functools.partial(
        _paged_kernel, scale=scale, block_s=bs, num_blocks=MBS,
        num_kv=KV, group=group)

    def kv_ix(b, ib, layer_ref, tables_ref, len_ref):
        del len_ref
        return (layer_ref[0], tables_ref[b, ib], 0, 0, 0)

    # the layer axis is squeezed out of the block: the body sees the
    # (1, bs, KV, D) block of one layer, as if the pool had no layer axis
    kv_spec = pl.BlockSpec((None, 1, bs, KV, D), kv_ix)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, MBS),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, ib, *_: (b, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, ib, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
        ],
    )

    with jax.named_scope("paged_decode_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="paged_decode_attention",
        )(jnp.asarray(layer, jnp.int32).reshape(1),
          tables.astype(jnp.int32), lengths.astype(jnp.int32),
          qh, k_pool, v_pool)

    return out.reshape(B, 1, H, D)


def paged_attention_reference(q, k_pool, v_pool, layer, tables, lengths, *,
                              scale: float):
    """XLA path (and the kernel's correctness oracle), same arguments as
    the kernel: gather the per-slot cache of one layer via the block
    table, then grouped-einsum attention. Used on CPU and as the
    non-Pallas fallback in ``models.paged_cache``."""
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
