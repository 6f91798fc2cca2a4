"""Flash-attention forward kernel (Pallas TPU).

Online-softmax tiling: grid = (batch·heads, q blocks, kv blocks) with the kv
dimension innermost ("arbitrary" = sequential), carrying the running max /
normalizer / accumulator in VMEM scratch so the S×S score matrix never touches
HBM. Causal blocks strictly above the diagonal are skipped with ``pl.when``
(compute is elided; the scratch state is carried through unchanged).

Layout contract: inputs are (B, H, S, D); GQA kv heads are resolved in the kv
BlockSpec index map (no materialized head repeat). Matmuls run on the MXU in
the input dtype with f32 accumulation (``preferred_element_type``).

The reference framework has no kernel layer (its attention lives in torch /
vLLM, outside the repo); this file is net-new TPU-first work (SURVEY.md §5
"Long-context": TPU-native plan).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF
from ray_tpu.util.profiling import part

_LANES = 128


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, block_q: int, block_kv: int,
                kv_len: int, num_kv_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Causal: q rows [iq·Bq, iq·Bq+Bq) never see kv cols >= (iq+1)·Bq, so
    # blocks strictly above the diagonal are skipped entirely.
    should_run = (ik * block_kv < (iq + 1) * block_q) if causal else True

    @pl.when(should_run)
    def _compute():
        q = q_ref[0]                      # (Bq, D)
        k = k_ref[0]                      # (Bkv, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (Bq, Bkv) f32

        col = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col < kv_len               # padded kv tail
        if causal:
            row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = jnp.logical_and(mask, row >= col)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                               # (Bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                              # (Bq, Bkv)
        alpha = jnp.exp(m_prev - m_new)                     # (Bq, 1)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        # Fully-masked rows (padding) would divide by zero; keep them finite.
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m_ref[:, :1] + jnp.log(l_safe))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def flash_attention_fwd_pallas(q, k, v, *, causal: bool, scale: float,
                               block_q: int = 512, block_kv: int = 512,
                               interpret: bool = False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).

    Returns ``(out, lse)``: out (B, Hq, Sq, D) in q.dtype, lse (B, Hq, Sq)
    f32 where ``lse[i] = log(sum_j exp(scale·q_i·k_j))`` over unmasked j.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv

    block_q = max(16, min(block_q, sq))
    block_kv = max(16, min(block_kv, skv))
    sq_p = math.ceil(sq / block_q) * block_q
    skv_p = math.ceil(skv / block_kv) * block_kv
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if skv_p != skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    nq = sq_p // block_q
    nk = skv_p // block_kv

    def q_index(bh, iq, ik):
        return (bh, iq, 0)

    def kv_index(bh, iq, ik):
        return (bh // hq * hkv + (bh % hq) // group, ik, 0)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, kv_len=skv, num_kv_blocks=nk)

    with part("flash_attention_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=(b * hq, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_index),
                pl.BlockSpec((1, block_kv, d), kv_index),
                pl.BlockSpec((1, block_kv, d), kv_index),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), q_index),
                pl.BlockSpec((1, block_q, _LANES),
                             lambda bh, iq, ik: (bh, iq, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * hq, sq_p, d), q.dtype),
                jax.ShapeDtypeStruct((b * hq, sq_p, _LANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="flash_attention_fwd",
        )(q.reshape(b * hq, sq_p, d),
          k.reshape(b * hkv, skv_p, d),
          v.reshape(b * hkv, skv_p, d))

    out = out.reshape(b, hq, sq_p, d)[:, :, :sq]
    lse = lse[:, :, 0].reshape(b, hq, sq_p)[:, :, :sq]
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels.
#
# Both kernels keep the score matrix *transposed* relative to the forward:
# s_t = K·Qᵀ of shape (block_kv, block_q). With q as the lane (minor)
# dimension, the per-q-row vectors lse and delta — stored as (1, block_q)
# tiles — broadcast against s_t without any in-kernel transpose; every
# contraction is a plain MXU dot_general.
#
# Standard recompute formulation (P recomputed from q, k, lse):
#   P   = exp(S·scale − lse)
#   dV  = Pᵀ·dO
#   dS  = P ∘ (dO·Vᵀ − Δ)   with Δ = Σ_d dO·O − dlse (precomputed, f32)
#   dQ  = scale·dS·K          dK = scale·dSᵀ·Q
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc_ref,
                   *, scale: float, causal: bool, block_q: int,
                   block_kv: int, q_len: int, kv_len: int,
                   num_kv_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    should_run = (ik * block_kv < (iq + 1) * block_q) if causal else True

    @pl.when(should_run)
    def _compute():
        q = q_ref[0]                       # (Bq, D)
        k = k_ref[0]                       # (Bkv, D)
        v = v_ref[0]
        do = do_ref[0]                     # (Bq, D)
        lse = lse_ref[0]                   # (1, Bq) f32
        delta = delta_ref[0]               # (1, Bq) f32

        s_t = jax.lax.dot_general(         # (Bkv, Bq) = K·Qᵀ
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 1)
        kpos = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
        mask = jnp.logical_and(qpos < q_len, kpos < kv_len)
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        p_t = jnp.where(mask, jnp.exp(s_t - lse), 0.0)        # (Bkv, Bq)
        dp_t = jax.lax.dot_general(        # (Bkv, Bq) = V·dOᵀ
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta)
        dq_acc_ref[:] += jax.lax.dot_general(   # (Bq, D) = dSᵀ_t·K·scale
            ds_t, k.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, scale: float, causal: bool, block_q: int,
                    block_kv: int, q_len: int, kv_len: int,
                    num_q_blocks: int, num_inner: int):
    ik = pl.program_id(1)
    e = pl.program_id(2)                   # enumerates (gqa group, q block)
    iq = e % num_q_blocks

    @pl.when(e == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    # Causal: the q block must reach at least the first kv row of this block.
    should_run = ((iq + 1) * block_q > ik * block_kv) if causal else True

    @pl.when(should_run)
    def _compute():
        q = q_ref[0]                       # (Bq, D)
        k = k_ref[0]                       # (Bkv, D)
        v = v_ref[0]
        do = do_ref[0]                     # (Bq, D)
        lse = lse_ref[0]                   # (1, Bq)
        delta = delta_ref[0]

        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (Bkv, Bq)
        qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 1)
        kpos = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
        mask = jnp.logical_and(qpos < q_len, kpos < kv_len)
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        p_t = jnp.where(mask, jnp.exp(s_t - lse), 0.0)
        dv_acc_ref[:] += jax.lax.dot_general(   # (Bkv, D) = P_t·dO
            p_t, do.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta)
        dk_acc_ref[:] += jax.lax.dot_general(   # (Bkv, D) = dS_t·Q·scale
            ds_t, q.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(e == num_inner - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def flash_attention_bwd_pallas(q, k, v, lse, delta, dout, *,
                               causal: bool, scale: float,
                               block_q: int = 512, block_kv: int = 512,
                               interpret: bool = False):
    """Backward pass. q/dout: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D);
    lse, delta: (B, Hq, Sq) f32 with delta = Σ_d dO·O − dlse.

    Returns (dq, dk, dv) in the input dtypes/shapes. GQA kv gradients are
    accumulated *inside* the dkv kernel (the innermost grid axis enumerates
    group × q-blocks against a resident kv tile) — no materialized
    head-repeat or post-hoc group reduction.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv

    block_q = max(16, min(block_q, sq))
    block_kv = max(16, min(block_kv, skv))
    sq_p = math.ceil(sq / block_q) * block_q
    skv_p = math.ceil(skv / block_kv) * block_kv
    if sq_p != sq:
        pad = ((0, 0), (0, 0), (0, sq_p - sq), (0, 0))
        q = jnp.pad(q, pad)
        dout = jnp.pad(dout, pad)
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, sq_p - sq)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, sq_p - sq)))
    if skv_p != skv:
        pad = ((0, 0), (0, 0), (0, skv_p - skv), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    nq = sq_p // block_q
    nk = skv_p // block_kv

    qf = q.reshape(b * hq, sq_p, d)
    doutf = dout.reshape(b * hq, sq_p, d)
    kf = k.reshape(b * hkv, skv_p, d)
    vf = v.reshape(b * hkv, skv_p, d)
    lsef = lse.reshape(b * hq, 1, sq_p).astype(jnp.float32)
    deltaf = delta.reshape(b * hq, 1, sq_p).astype(jnp.float32)

    def q_ix(bh, iq, ik):
        return (bh, iq, 0)

    def kv_ix(bh, iq, ik):
        return (bh // hq * hkv + (bh % hq) // group, ik, 0)

    def vec_ix(bh, iq, ik):
        return (bh, 0, iq)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, q_len=sq, kv_len=skv, num_kv_blocks=nk)

    with part("flash_attention_dq"):
        dq = pl.pallas_call(
            dq_kernel,
            grid=(b * hq, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_ix),
                pl.BlockSpec((1, block_kv, d), kv_ix),
                pl.BlockSpec((1, block_kv, d), kv_ix),
                pl.BlockSpec((1, block_q, d), q_ix),
                pl.BlockSpec((1, 1, block_q), vec_ix),
                pl.BlockSpec((1, 1, block_q), vec_ix),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), q_ix),
            out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="flash_attention_dq",
        )(qf, kf, vf, doutf, lsef, deltaf)

    # dkv: grid minor axis sweeps (group, q block) pairs while one kv tile
    # and its dk/dv accumulators stay resident in VMEM.
    num_inner = group * nq

    def q_ix2(bh, ik, e):
        return (bh // hkv * hq + (bh % hkv) * group + e // nq, e % nq, 0)

    def kv_ix2(bh, ik, e):
        return (bh, ik, 0)

    def vec_ix2(bh, ik, e):
        return (bh // hkv * hq + (bh % hkv) * group + e // nq, 0, e % nq)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, q_len=sq, kv_len=skv, num_q_blocks=nq,
        num_inner=num_inner)

    with part("flash_attention_dkv"):
        dk, dv = pl.pallas_call(
            dkv_kernel,
            grid=(b * hkv, nk, num_inner),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_ix2),
                pl.BlockSpec((1, block_kv, d), kv_ix2),
                pl.BlockSpec((1, block_kv, d), kv_ix2),
                pl.BlockSpec((1, block_q, d), q_ix2),
                pl.BlockSpec((1, 1, block_q), vec_ix2),
                pl.BlockSpec((1, 1, block_q), vec_ix2),
            ],
            out_specs=[
                pl.BlockSpec((1, block_kv, d), kv_ix2),
                pl.BlockSpec((1, block_kv, d), kv_ix2),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * hkv, skv_p, d), k.dtype),
                jax.ShapeDtypeStruct((b * hkv, skv_p, d), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_kv, d), jnp.float32),
                pltpu.VMEM((block_kv, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="flash_attention_dkv",
        )(qf, kf, vf, doutf, lsef, deltaf)

    dq = dq.reshape(b, hq, sq_p, d)[:, :, :sq]
    dk = dk.reshape(b, hkv, skv_p, d)[:, :, :skv]
    dv = dv.reshape(b, hkv, skv_p, d)[:, :, :skv]
    return dq, dk, dv
