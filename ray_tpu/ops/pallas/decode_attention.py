"""Flash-decoding attention kernel (Pallas TPU): one new query token per
slot against that slot's KV cache.

The serving engine's decode step is HBM-bandwidth-bound: every step
streams the whole KV cache once. This kernel keeps the running softmax
state in VMEM while the cache streams through in blocks (online softmax,
same recurrence as the training kernel in ``flash_attention.py``) and
handles GQA by an unrolled static loop over kv heads INSIDE the program:
each (slot, seq-block) grid step copies its cache block once and every
kv head consumes its slice — no head-repeated cache materialization and
no per-kv-head re-streaming. (The kv-head axis cannot be a grid
dimension with a (…, 1, D) block: Mosaic requires the last two block
dims be tile-aligned or span the array, and KV is small and unaligned.)

Layout contract: q (B, 1, H, D); k/v cache (B, S, KV, D); lengths (B,)
int32 (valid prefix incl. the new token). Grid = (B, S blocks) with the
S dimension sequential; lengths ride as a scalar-prefetch operand (the
whole array in SMEM, indexed by program id — a per-program (1,1) SMEM
block would violate Mosaic's last-two-dims tiling rule).

Net-new vs the reference (its serving attention lives in vLLM's paged
kernels, outside the repo); this is the TPU analog of flash-decoding.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention


NEG_INF = -1e30
_LANES = 128


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref,
                   *, scale: float, block_s: int, num_s_blocks: int,
                   num_kv: int, group: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    length = len_ref[pl.program_id(0)]
    # blocks wholly past the valid prefix contribute nothing
    @pl.when(ik * block_s < length)
    def _compute():
        for j in range(num_kv):          # static unroll over kv heads
            lo, hi = j * group, (j + 1) * group
            q = q_ref[0, lo:hi, :]       # (group, D)
            k = k_ref[0, :, j, :]        # (Bs, D)
            v = v_ref[0, :, j, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (group, Bs)
            col = ik * block_s + jax.lax.broadcasted_iota(
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

    @pl.when(ik == num_s_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, scale: float,
                     block_s: int = 512, interpret: bool = False):
    """q: (B, 1, H, D); k/v_cache: (B, S, KV, D); lengths: (B,) int32.
    Returns (B, 1, H, D) in q.dtype."""
    B, _, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    group = H // KV

    block_s = max(16, min(block_s, S))
    s_p = math.ceil(S / block_s) * block_s
    if s_p != S:
        pad = ((0, 0), (0, s_p - S), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
    ns = s_p // block_s

    # queries laid out (B, H, D) with kv-head groups contiguous in H
    qh = q.reshape(B, H, D)

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_s=block_s, num_s_blocks=ns,
        num_kv=KV, group=group)

    # lengths ride as a scalar-prefetch operand (whole array in SMEM,
    # indexed by program id) — a (1,1) SMEM block would violate the
    # last-two-dims tiling rule
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, ns),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, ik, *_: (b, 0, 0)),
            pl.BlockSpec((1, block_s, KV, D),
                         lambda b, ik, *_: (b, ik, 0, 0)),
            pl.BlockSpec((1, block_s, KV, D),
                         lambda b, ik, *_: (b, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, ik, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qh, k_cache, v_cache)

    return out.reshape(B, 1, H, D)


def slot_decode(q, k_cache, v_cache, lengths, *, scale: float):
    """The kernel on a TPU; elsewhere its oracle,
    :func:`ray_tpu.ops.attention.attend_rows` with each slot's query at
    its last row."""
    if attention.on_tpu():
        return decode_attention(q, k_cache, v_cache, lengths, scale=scale)
    return attention.attend_rows(q, k_cache, v_cache, lengths[:, None] - 1,
                                 scale)
