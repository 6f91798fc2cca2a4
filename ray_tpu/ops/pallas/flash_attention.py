"""Flash-attention kernels (Pallas TPU): forward, dq, dkv.

Online-softmax tiling: the S×S score matrix never touches HBM. The forward
and dq walk a work list, ``grid = (batch·heads, live block pairs)``: two
scalar-prefetched tables name the (q block, kv block) pair of every step, so
a causal call takes a step, and fetches a K / V block, only for a pair on or
below the diagonal, and a non-causal call for the whole rectangle. The steps
of one q block are consecutive ("arbitrary" = sequential) and carry its
running max / normalizer / accumulator in VMEM scratch.

All three kernels keep the scores *transposed*, s_t = K·Qᵀ of shape
(block_kv, block_q): with q as the lane (minor) dimension the per-query
vectors (running max and sum, lse, delta) are (1, block_q) rows that
broadcast against s_t over sublanes, and the forward's two reductions run
over sublanes, not along lanes. A mask is built only in a block it can
change: one the diagonal crosses, or the padded last kv block.

Layout contract: inputs are (B, H, S, D); GQA kv heads are resolved in the kv
BlockSpec index map (no materialized head repeat). Matmuls run on the MXU in
the input dtype with f32 accumulation (``preferred_element_type``). The
forward takes value rows of another width than the key rows (a served
prompt of a model with keys 192 and values 128 wide); the backward kernels
take one width, as the train step has.

The reference framework has no kernel layer (its attention lives in torch /
vLLM, outside the repo); this file is net-new TPU-first work (SURVEY.md §5
"Long-context": TPU-native plan).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF
from ray_tpu.util.profiling import part

_LANES = 128


def _blocks(block, length: int, row_bytes: int) -> tuple[int, int]:
    """(block, padded length) of one sequence axis. A sequence of at most
    one block is one block of whole lanes. ``block=None`` chooses: 1024 or
    512, whichever pads less. 1024² is the largest score tile whose f32
    temporaries (dq holds four) fit the VMEM a kernel may scope beside
    (block, D) operands of at most 256 bytes a row; on a v5e at 4,096 × 128
    it is the fastest for all three kernels (forward 4.40 ms a call against
    4.88 at 512², dq 4.51 / 5.31, dkv 6.03 / 6.55), and 2048 on either side
    is slower again (PERF.md section 6, PR 37). A row of 192 x 2 bytes (the
    forward alone, a served prompt) fits 1024² too and is no faster for it:
    1.414 ms against 1.394 at 512² for 64 heads x 2,048, 0.472 against 0.426
    for 64 x 1,024 (PERF.md section 6, PR 44)."""
    if block is None:
        block = 512
        if row_bytes <= 256 and -length % 1024 <= -length % 512:
            block = 1024
    block = min(block, math.ceil(length / _LANES) * _LANES)
    return block, math.ceil(length / block) * block


def _live(iq, ik, *, causal: bool, block_q: int, block_kv: int):
    """Whether block pair (iq, ik) holds work: always, or in a causal call
    when the kv block starts at or before the q block's last row."""
    return ik * block_kv < (iq + 1) * block_q if causal else True


def _work_list(nq: int, nk: int, **blocks):
    """The block pairs that hold work, q-major, as two int32 tables for
    scalar prefetch: ``(iq[t], ik[t])`` is step t's pair."""
    iq, ik = np.nonzero(np.broadcast_to(
        _live(np.arange(nq)[:, None], np.arange(nk)[None, :], **blocks),
        (nq, nk)))
    return jnp.asarray(iq, jnp.int32), jnp.asarray(ik, jnp.int32)


def _is_last(iq, ik, *, num_kv_blocks: int, **blocks):
    """Whether (iq, ik) is q block iq's last pair in the work list."""
    return jnp.logical_or(ik == num_kv_blocks - 1,
                          jnp.logical_not(_live(iq, ik + 1, **blocks)))


def _when_masked(step, iq, ik, *, causal: bool, block_q: int, block_kv: int,
                 kv_len: int, num_kv_blocks: int, span: int = 1):
    """Run ``step(mask)`` once. ``mask`` is a function of s_t (block_kv,
    block_q) where a mask can change a score: the diagonal crosses the block
    (its last key lies after its first query), or it is the padded last kv
    block. Elsewhere it is None and the body builds no iota or compare.

    ``span`` > 1 (a power of two that divides both blocks): a causal call
    is causal between runs of ``span`` positions and full inside a run, so
    a query sees the keys up to the last of its own run (``q | (span - 1)``).
    The runs lie inside the blocks, so which pairs hold work and which the
    diagonal crosses is as for ``span`` 1."""

    def mask(s_t):
        kpos = ik * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s_t.shape, 0)
        valid = kpos < kv_len
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s_t.shape, 1)
            if span > 1:
                qpos = qpos | (span - 1)
            valid = jnp.logical_and(valid, qpos >= kpos)
        return valid

    conds = []
    if causal:
        conds.append((ik + 1) * block_kv - 1 > iq * block_q)
    if kv_len != num_kv_blocks * block_kv:
        conds.append(ik == num_kv_blocks - 1)
    if not conds:
        step(None)
        return
    needs_mask = functools.reduce(jnp.logical_or, conds)
    pl.when(needs_mask)(lambda: step(mask))
    pl.when(jnp.logical_not(needs_mask))(lambda: step(None))


def _fwd_kernel(iq_ref, ik_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                qs_ref, acc_ref, m_ref, l_ref,
                *, scale: float, kv_len: int, num_kv_blocks: int,
                span: int = 1, **blocks):
    t = pl.program_id(1)
    iq = iq_ref[t]
    ik = ik_ref[t]

    @pl.when(ik == 0)
    def _init():
        # the scale goes once on the (Bq, Dk) queries, not on every
        # (Bkv, Bq) tile of scores
        qs_ref[:] = (q_ref[0].astype(jnp.float32) * scale).astype(
            qs_ref.dtype)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _step(mask):
        k = k_ref[0]                       # (Bkv, Dk)
        v = v_ref[0]                       # (Bkv, Dv)
        s_t = jax.lax.dot_general(         # (Bkv, Bq) = K·(scale·Q)ᵀ
            k, qs_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if mask is not None:
            s_t = jnp.where(mask(s_t), s_t, NEG_INF)
        m_prev = m_ref[:]                                   # (1, Bq)
        m_new = jnp.maximum(m_prev, jnp.max(s_t, axis=0, keepdims=True))
        p_t = jnp.exp(s_t - m_new)                          # (Bkv, Bq)
        alpha = jnp.exp(m_prev - m_new)                     # (1, Bq)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p_t, axis=0, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            v, p_t.astype(v.dtype), (((0,), (0,)), ((), ())),   # (Dv, Bq)
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    _when_masked(_step, iq, ik, kv_len=kv_len, num_kv_blocks=num_kv_blocks,
                 span=span, **blocks)

    @pl.when(_is_last(iq, ik, num_kv_blocks=num_kv_blocks, **blocks))
    def _finalize():
        l = l_ref[:]
        # Fully-masked rows (padding) would divide by zero; keep them finite.
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).T.astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l == 0.0, NEG_INF, m_ref[:] + jnp.log(l_safe))


def flash_attention_fwd_pallas(q, k, v, *, causal: bool, scale: float,
                               block_q: int | None = None,
                               block_kv: int | None = None,
                               span: int = 1,
                               interpret: bool = False):
    """q: (B, Hq, Sq, Dk); k: (B, Hkv, Skv, Dk); v: (B, Hkv, Skv, Dv).

    Returns ``(out, lse)``: out (B, Hq, Sq, Dv) in q.dtype, lse (B, Hq, Sq)
    f32 where ``lse[i] = log(sum_j exp(scale·q_i·k_j))`` over unmasked j.
    The blocks are chosen from the shapes (``_blocks``, by the wider of
    the two rows) unless given. ``span`` > 1 with ``causal``: causal
    between runs of ``span`` positions, full inside one (a power of two,
    at most the lanes, so that it divides every block).
    """
    b, hq, sq, dk = q.shape
    _, hkv, skv, dv = v.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if span != 1 and (not causal or span & (span - 1) or span > _LANES):
        raise ValueError(f"span {span}: a power of two up to {_LANES}, in "
                         "a causal call")
    group = hq // hkv

    row_bytes = max(dk, dv) * q.dtype.itemsize
    block_q, sq_p = _blocks(block_q, sq, row_bytes)
    block_kv, skv_p = _blocks(block_kv, skv, row_bytes)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if skv_p != skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    nk = skv_p // block_kv
    blocks = dict(causal=causal, block_q=block_q, block_kv=block_kv)
    iq_tab, ik_tab = _work_list(sq_p // block_q, nk, **blocks)

    def q_index(bh, t, iq_ref, ik_ref):
        return (bh, iq_ref[t], 0)

    def kv_index(bh, t, iq_ref, ik_ref):
        return (bh // hq * hkv + (bh % hq) // group, ik_ref[t], 0)

    def lse_index(bh, t, iq_ref, ik_ref):
        return (bh, 0, iq_ref[t])

    kernel = functools.partial(
        _fwd_kernel, scale=scale, kv_len=skv, num_kv_blocks=nk, span=span,
        **blocks)

    with part("flash_attention_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b * hq, iq_tab.shape[0]),
                in_specs=[
                    pl.BlockSpec((1, block_q, dk), q_index),
                    pl.BlockSpec((1, block_kv, dk), kv_index),
                    pl.BlockSpec((1, block_kv, dv), kv_index),
                ],
                out_specs=[
                    pl.BlockSpec((1, block_q, dv), q_index),
                    pl.BlockSpec((1, 1, block_q), lse_index),
                ],
                scratch_shapes=[
                    pltpu.VMEM((block_q, dk), q.dtype),
                    pltpu.VMEM((dv, block_q), jnp.float32),
                    pltpu.VMEM((1, block_q), jnp.float32),
                    pltpu.VMEM((1, block_q), jnp.float32),
                ]),
            out_shape=[
                jax.ShapeDtypeStruct((b * hq, sq_p, dv), q.dtype),
                jax.ShapeDtypeStruct((b * hq, 1, sq_p), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="flash_attention_fwd",
        )(iq_tab, ik_tab,
          q.reshape(b * hq, sq_p, dk),
          k.reshape(b * hkv, skv_p, dk),
          v.reshape(b * hkv, skv_p, dv))

    out = out.reshape(b, hq, sq_p, dv)[:, :, :sq]
    lse = lse.reshape(b, hq, sq_p)[:, :, :sq]
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels.
#
# Standard recompute formulation (P recomputed from q, k, lse):
#   P   = exp(S·scale − lse)
#   dV  = Pᵀ·dO
#   dS  = P ∘ (dO·Vᵀ − Δ)   with Δ = Σ_d dO·O − dlse (precomputed, f32)
#   dQ  = scale·dS·K          dK = scale·dSᵀ·Q
# lse and delta come as (1, block_q) tiles and broadcast against s_t without
# any in-kernel transpose; every contraction is a plain MXU dot_general.
# Only key positions are masked: a padded query row is zeros in q and dO
# with lse = delta = 0, so its P is 1, its dS 0, and it adds nothing.
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(iq_ref, ik_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_acc_ref,
                   *, scale: float, kv_len: int, num_kv_blocks: int,
                   **blocks):
    t = pl.program_id(1)
    iq = iq_ref[t]
    ik = ik_ref[t]

    @pl.when(ik == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    def _step(mask):
        q = q_ref[0]                       # (Bq, D)
        k = k_ref[0]                       # (Bkv, D)
        v = v_ref[0]
        do = do_ref[0]                     # (Bq, D)
        lse = lse_ref[0]                   # (1, Bq) f32
        delta = delta_ref[0]               # (1, Bq) f32

        s_t = jax.lax.dot_general(         # (Bkv, Bq) = K·Qᵀ
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p_t = jnp.exp(s_t - lse)                              # (Bkv, Bq)
        if mask is not None:
            p_t = jnp.where(mask(s_t), p_t, 0.0)
        dp_t = jax.lax.dot_general(        # (Bkv, Bq) = V·dOᵀ
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta)
        dq_acc_ref[:] += jax.lax.dot_general(   # (Bq, D) = dSᵀ_t·K·scale
            ds_t, k.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    _when_masked(_step, iq, ik, kv_len=kv_len, num_kv_blocks=num_kv_blocks,
                 **blocks)

    @pl.when(_is_last(iq, ik, num_kv_blocks=num_kv_blocks, **blocks))
    def _finalize():
        dq_ref[0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, scale: float, kv_len: int, num_kv_blocks: int,
                    num_q_blocks: int, num_inner: int, **blocks):
    ik = pl.program_id(1)
    e = pl.program_id(2)                   # enumerates (gqa group, q block)
    iq = e % num_q_blocks

    @pl.when(e == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    def _step(mask):
        q = q_ref[0]                       # (Bq, D)
        k = k_ref[0]                       # (Bkv, D)
        v = v_ref[0]
        do = do_ref[0]                     # (Bq, D)
        lse = lse_ref[0]                   # (1, Bq)
        delta = delta_ref[0]

        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (Bkv, Bq)
        p_t = jnp.exp(s_t - lse)
        if mask is not None:
            p_t = jnp.where(mask(s_t), p_t, 0.0)
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

    # a q block above the diagonal is a step with nothing to do
    pl.when(_live(iq, ik, **blocks))(lambda: _when_masked(
        _step, iq, ik, kv_len=kv_len, num_kv_blocks=num_kv_blocks, **blocks))

    @pl.when(e == num_inner - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def flash_attention_bwd_pallas(q, k, v, lse, delta, dout, *,
                               causal: bool, scale: float,
                               block_q: int | None = None,
                               block_kv: int | None = None,
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

    block_q, sq_p = _blocks(block_q, sq, d * q.dtype.itemsize)
    block_kv, skv_p = _blocks(block_kv, skv, d * q.dtype.itemsize)
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
    blocks = dict(causal=causal, block_q=block_q, block_kv=block_kv)
    iq_tab, ik_tab = _work_list(nq, nk, **blocks)

    qf = q.reshape(b * hq, sq_p, d)
    doutf = dout.reshape(b * hq, sq_p, d)
    kf = k.reshape(b * hkv, skv_p, d)
    vf = v.reshape(b * hkv, skv_p, d)
    lsef = lse.reshape(b * hq, 1, sq_p).astype(jnp.float32)
    deltaf = delta.reshape(b * hq, 1, sq_p).astype(jnp.float32)

    def q_ix(bh, t, iq_ref, ik_ref):
        return (bh, iq_ref[t], 0)

    def kv_ix(bh, t, iq_ref, ik_ref):
        return (bh // hq * hkv + (bh % hq) // group, ik_ref[t], 0)

    def vec_ix(bh, t, iq_ref, ik_ref):
        return (bh, 0, iq_ref[t])

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, kv_len=skv, num_kv_blocks=nk, **blocks)

    with part("flash_attention_dq"):
        dq = pl.pallas_call(
            dq_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b * hq, iq_tab.shape[0]),
                in_specs=[
                    pl.BlockSpec((1, block_q, d), q_ix),
                    pl.BlockSpec((1, block_kv, d), kv_ix),
                    pl.BlockSpec((1, block_kv, d), kv_ix),
                    pl.BlockSpec((1, block_q, d), q_ix),
                    pl.BlockSpec((1, 1, block_q), vec_ix),
                    pl.BlockSpec((1, 1, block_q), vec_ix),
                ],
                out_specs=pl.BlockSpec((1, block_q, d), q_ix),
                scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="flash_attention_dq",
        )(iq_tab, ik_tab, qf, kf, vf, doutf, lsef, deltaf)

    # dkv: grid minor axis sweeps (group, q block) pairs while one kv tile
    # and its dk/dv accumulators stay resident in VMEM. A q block above the
    # diagonal is named as the first one below it, which the sweep reads
    # next: the step that does nothing fetches nothing of its own.
    num_inner = group * nq

    def q_block(ik, e):
        iq = e % nq
        if causal:
            iq = jnp.maximum(iq, jnp.minimum(ik * block_kv // block_q, nq - 1))
        return iq

    def q_ix2(bh, ik, e):
        return (bh // hkv * hq + (bh % hkv) * group + e // nq,
                q_block(ik, e), 0)

    def kv_ix2(bh, ik, e):
        return (bh, ik, 0)

    def vec_ix2(bh, ik, e):
        return (bh // hkv * hq + (bh % hkv) * group + e // nq, 0,
                q_block(ik, e))

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, kv_len=skv, num_kv_blocks=nk,
        num_q_blocks=nq, num_inner=num_inner, **blocks)

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
