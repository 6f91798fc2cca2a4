"""Attention frontend: dispatch + differentiable flash attention.

``flash_attention`` takes (B, S, H, D) activations, dispatches the forward to
the Pallas TPU kernel (``ops/pallas/flash_attention.py``) on TPU backends and
to a fused XLA reference elsewhere, and installs a memory-efficient blockwise
backward via ``jax.custom_vjp`` (two ``lax.scan`` passes, materializing at
most an S×block score tile at a time — never the S×S matrix).

Net-new relative to the reference framework, which ships no attention
implementation (SURVEY.md §2.3/§5: long-context delegated to vLLM).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.util.profiling import part

NEG_INF = -1e30


def on_tpu() -> bool:
    """The one question the kernel dispatchers ask. A backend that fails
    to start raises here — it never turns into the reference path."""
    return jax.default_backend() == "tpu"


@part("attention")
def mha_reference(q, k, v, causal: bool = True, scale: Optional[float] = None):
    """Naive O(S²)-memory attention, (B, S, H, D) layout. Test oracle."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    hq, hkv = q.shape[2], k.shape[2]
    if hq != hkv:
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, skv = s.shape[-2], s.shape[-1]
        row = jnp.arange(sq)[:, None]
        col = jnp.arange(skv)[None, :]
        s = jnp.where(row >= col, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


@part("attention")
def attend_rows(q, ks, vs, q_pos, scale):
    """q (B, C, H, D) over the row sets ks / vs (B, S, KV, D): key ``j``
    is visible to query ``i`` of row ``b`` iff ``j <= q_pos[b, i]``
    (absolute positions, so rows past a slot's length, stale or zero,
    are never seen).

    A GROUPED einsum (q reshaped (B, C, KV, group, D)), so the rows are
    never materialized head-repeated — on a (slots, S, KV, D) cache that
    repeat was group x cache-size of wasted HBM traffic per step."""
    B, C, H, D = q.shape
    S, KV = ks.shape[1:3]
    qg = q.astype(jnp.float32).reshape(B, C, KV, H // KV, D)
    s = jnp.einsum("bckgd,bskd->bkgcs", qg, ks.astype(jnp.float32)) * scale
    allowed = jnp.arange(S)[None, None, :] <= q_pos[:, :, None]   # (B,C,S)
    s = jnp.where(allowed[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgcs,bskd->bckgd", p, vs.astype(jnp.float32))
    return out.reshape(B, C, H, D).astype(q.dtype)


def _sink_softmax(s, sink):
    """Softmax over the last axis of ``s`` (..., H-major as given) with an
    optional per-head sink logit in the denominator only: the sink takes
    probability mass and adds no value. ``sink`` broadcasts against
    ``s[..., 0]``."""
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink[..., None])
    p = jnp.exp(s - m)
    den = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sink[..., None] - m)
    return p / den


@part("attention")
def hybrid_attention_reference(q, k, v, *, scale: float,
                               window: Optional[int] = None, sink=None,
                               span: int = 1):
    """Causal attention in XLA for one layer of a model that mixes full
    and sliding-window layers: q (B, S, H, Dk), k (B, S, KV, Dk), v
    (B, S, KV, Dv) with ``Dv`` free of ``Dk``; -> (B, S, H, Dv) in
    q.dtype, accumulated in float32.

    ``window`` w: query i attends keys j with ``i - w < j <= i``. The
    scores are then computed in bands: queries in blocks of w against
    their own key block and the one before, so key blocks wholly outside
    the window are never read and the temporaries are (S, 2w), not
    (S, S). ``sink`` (H,) float32: a learned per-head logit added to the
    softmax's denominator. ``span`` > 1 (full layers only): causal between
    runs of ``span`` positions and full inside a run, query i attends keys
    ``j // span <= i // span``."""
    B, S, H, Dk = q.shape
    KV, Dv = k.shape[2], v.shape[-1]
    g = H // KV
    qf = q.astype(jnp.float32).reshape(B, S, KV, g, Dk)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    sk = None if sink is None else sink.astype(jnp.float32).reshape(KV, g)
    if window is None or window >= S:
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf) * scale
        row = jnp.arange(S)[:, None]
        col = jnp.arange(S)[None, :]
        ok = row >= col if span == 1 else row // span >= col // span
        if window is not None:
            ok &= row - col < window
        s = jnp.where(ok, s, NEG_INF)
        p = _sink_softmax(s, None if sk is None else sk[None, :, :, None])
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, vf)
        return out.reshape(B, S, H, Dv).astype(q.dtype)
    w = window
    nb = -(-S // w)
    pad = nb * w - S
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = qf.reshape(B, nb, w, KV, g, Dk)

    def band(x):                       # (B, nb*w, KV, D) -> (B, nb, 2w, ...)
        xb = x.reshape(B, nb, w, KV, x.shape[-1])
        prev = jnp.pad(xb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
        return jnp.concatenate([prev, xb], axis=2)

    s = jnp.einsum("bnqkgd,bnskd->bnkgqs", qb, band(kf)) * scale
    row = jnp.arange(w)[:, None] + w          # position in the band
    col = jnp.arange(2 * w)[None, :]
    ok = (row >= col) & (row - col < w)
    first = (jnp.arange(nb) == 0)[:, None, None] & (col < w)[None]
    ok = ok[None] & ~first                    # block 0 has no block before
    s = jnp.where(ok[None, :, None, None], s, NEG_INF)
    p = _sink_softmax(
        s, None if sk is None else sk[None, None, :, :, None])
    out = jnp.einsum("bnkgqs,bnskd->bnqkgd", p, band(vf))
    return out.reshape(B, nb * w, H, Dv)[:, :S].astype(q.dtype)


@part("attention")
def prompt_attention(q, k, v, *, scale: float, window: Optional[int] = None,
                     sink=None, span: int = 1):
    """Causal attention of a served prompt over itself, one layer:
    :func:`hybrid_attention_reference`'s arguments and result. A full
    layer without a sink (``window`` None or not shorter than the
    prompt) goes through the flash forward kernel on a TPU: operands in
    their own dtype on the MXU, float32 running maximum, sum and
    accumulator, no (S, S) array. A window or a sink, which the kernel
    has not, and every other backend take the XLA reference. ``span``
    > 1 (a model that generates by blocks of that many positions): the
    mask is causal between blocks and full inside one, in the kernel's
    diagonal tiles as in the reference."""
    if span != 1 and window is not None:
        raise ValueError("a block-causal layer has no window")
    if (on_tpu() and sink is None
            and (window is None or window >= q.shape[1])):
        from ray_tpu.ops.pallas.flash_attention import flash_attention_fwd_pallas

        out, _ = flash_attention_fwd_pallas(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, scale=scale, span=span)
        return out.transpose(0, 2, 1, 3)
    return hybrid_attention_reference(q, k, v, scale=scale, window=window,
                                      sink=sink, span=span)


def _fwd_xla(q, k, v, causal, scale):
    """Fused full-matrix forward returning (out, lse); (B, H, S, D) layout.

    Used off-TPU (tests, CPU dry-runs) where VMEM tiling doesn't apply.
    """
    if q.shape[1] != k.shape[1]:  # GQA
        k = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
        v = jnp.repeat(v, q.shape[1] // v.shape[1], axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        row = jnp.arange(s.shape[-2])[:, None]
        col = jnp.arange(s.shape[-1])[None, :]
        s = jnp.where(row >= col, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)  # all-masked rows
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p / jnp.maximum(l, 1e-30),
                     v.astype(jnp.float32))
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_lse(q, k, v, causal, scale, block):
    """Joint (out, lse) primitive so downstream consumers of lse (ring
    attention merges) stay differentiable: bwd handles the dlse cotangent
    via the extra ``P·dlse`` term in dS."""
    return _flash_fwd_dispatch(q, k, v, causal, scale)


def _flash_fwd_dispatch(q, k, v, causal, scale):
    if on_tpu():
        from ray_tpu.ops.pallas.flash_attention import flash_attention_fwd_pallas

        return flash_attention_fwd_pallas(q, k, v, causal=causal, scale=scale)
    return _fwd_xla(q, k, v, causal, scale)


def _flash_lse_fwd(q, k, v, causal, scale, block):
    out, lse = _flash_fwd_dispatch(q, k, v, causal, scale)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, scale, block, res, cotangents):
    """Backward dispatch: Pallas TPU kernels on TPU, blockwise XLA scan
    elsewhere. Both compute the standard recompute-form flash backward."""
    if on_tpu():
        from ray_tpu.ops.pallas.flash_attention import flash_attention_bwd_pallas

        dout, dlse = cotangents
        q, k, v, out, lse = res
        delta = (jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                         axis=-1) - dlse.astype(jnp.float32))
        dq, dk, dv = flash_attention_bwd_pallas(
            q, k, v, lse, delta, dout, causal=causal, scale=scale)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
    return _flash_bwd_xla(causal, scale, block, res, cotangents)


def _flash_bwd_xla(causal, scale, block, res, cotangents):
    """Blockwise flash backward, (B, H, S, D) layout.

    Standard recompute formulation: with P = exp(S·scale − lse) and
    Δ_i = Σ_d dO_id·O_id,
        dV = Pᵀ·dO,  dS = P ∘ (dO·Vᵀ − Δ + dlse),  dQ = scale·dS·K,
        dK = scale·dSᵀ·Q  (the dlse term makes the lse output differentiable).
    Pass 1 scans kv blocks accumulating dQ; pass 2 scans q blocks
    accumulating dK/dV — each step touches only an S×block tile.
    """
    dout, dlse = cotangents
    q, k, v, out, lse = res
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)

    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    do = dout.astype(jnp.float32)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1) \
        - dlse.astype(jnp.float32)                               # (B,H,Sq)

    blk = min(block, sq, skv)
    nkv = -(-skv // blk)
    nq = -(-sq // blk)
    skv_p, sq_p = nkv * blk, nq * blk
    pad_kv = [(0, 0), (0, 0), (0, skv_p - skv), (0, 0)]
    pad_q = [(0, 0), (0, 0), (0, sq_p - sq), (0, 0)]
    kp = jnp.pad(kf, pad_kv)
    vp = jnp.pad(vf, pad_kv)
    qp = jnp.pad(qf, pad_q)
    dop = jnp.pad(do, pad_q)
    lsep = jnp.pad(lse, [(0, 0), (0, 0), (0, sq_p - sq)],
                   constant_values=NEG_INF)
    deltap = jnp.pad(delta, [(0, 0), (0, 0), (0, sq_p - sq)])

    row_q = jnp.arange(sq)
    col_kv = jnp.arange(skv_p)

    def scores(qb, kb):
        return jnp.einsum("bhqd,bhkd->bhqk", qb, kb) * scale

    # Pass 1: dQ — scan over kv blocks against the full (unpadded) q.
    kvb = kp.reshape(b, hq, nkv, blk, d).transpose(2, 0, 1, 3, 4)
    vvb = vp.reshape(b, hq, nkv, blk, d).transpose(2, 0, 1, 3, 4)

    def dq_step(dq_acc, xs):
        i, kb, vb = xs
        col = i * blk + jnp.arange(blk)
        s = scores(qf, kb)                                   # (B,H,Sq,blk)
        mask = (col[None, :] < skv)
        if causal:
            mask = mask & (row_q[:, None] >= col[None, :])
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, vb)
        ds = p * (dp - delta[..., None])
        return dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, kb) * scale, None

    dq, _ = jax.lax.scan(
        dq_step, jnp.zeros_like(qf),
        (jnp.arange(nkv), kvb, vvb))

    # Pass 2: dK/dV — scan over q blocks against the full (padded) k/v.
    qb_ = qp.reshape(b, hq, nq, blk, d).transpose(2, 0, 1, 3, 4)
    dob_ = dop.reshape(b, hq, nq, blk, d).transpose(2, 0, 1, 3, 4)
    lseb_ = lsep.reshape(b, hq, nq, blk).transpose(2, 0, 1, 3)
    deltab_ = deltap.reshape(b, hq, nq, blk).transpose(2, 0, 1, 3)

    def dkv_step(carry, xs):
        dk_acc, dv_acc = carry
        i, qb, dob, lseb, deltab = xs
        row = i * blk + jnp.arange(blk)
        s = scores(qb, kp)                                   # (B,H,blk,Skv_p)
        mask = (row[:, None] < sq) & (col_kv[None, :] < skv)
        if causal:
            mask = mask & (row[:, None] >= col_kv[None, :])
        p = jnp.where(mask, jnp.exp(s - lseb[..., None]), 0.0)
        dv_acc = dv_acc + jnp.einsum("bhqk,bhqd->bhkd", p, dob)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dob, vp)
        ds = p * (dp - deltab[..., None])
        dk_acc = dk_acc + jnp.einsum("bhqk,bhqd->bhkd", ds, qb) * scale
        return (dk_acc, dv_acc), None

    (dkp, dvp), _ = jax.lax.scan(
        dkv_step, (jnp.zeros_like(kp), jnp.zeros_like(vp)),
        (jnp.arange(nq), qb_, dob_, lseb_, deltab_))
    dk = dkp[:, :, :skv]
    dv = dvp[:, :, :skv]

    if group > 1:
        dk = dk.reshape(b, hkv, group, skv, d).sum(axis=2)
        dv = dv.reshape(b, hkv, group, skv, d).sum(axis=2)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


@part("attention")
def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None, block: int = 512):
    """Differentiable flash attention, (B, S, H, D) layout (GQA-aware).

    ``block`` tiles the blockwise XLA backward used off-TPU; the Pallas
    kernels choose their blocks from the call's shapes."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out, _ = _flash_lse(qt, kt, vt, causal, scale, block)
    return out.transpose(0, 2, 1, 3)


def flash_attention_with_lse(q, k, v, causal: bool = True,
                             scale: Optional[float] = None, block: int = 512):
    """Differentiable variant returning (out, lse) in (B, S, H, D) /
    (B, H, S) layouts; building block for ring attention."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    out, lse = _flash_lse(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal, scale, block)
    return out.transpose(0, 2, 1, 3), lse
