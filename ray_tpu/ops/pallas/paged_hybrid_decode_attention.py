"""Paged decode attention for a model that mixes full and sliding-window
layers (Pallas TPU): one new query token per slot against that slot's
paged KV, where a key is not as wide as a value, a window layer reads
only the blocks its window still touches, and a learned per-head sink
logit may sit in the softmax's denominator.

A second kernel beside ``paged_decode_attention.py`` and not an
extension of it: the dense pool is (L, NB, bs, KV, D) with one width for
keys and values, and its kernel's numbers are the benchmark's baseline.
Here the pools are lane-dense, one row a token:

    k_pool   (L, NB, bs, Wk)       every kv head's key, packed by the
                                   model in chunks of ``c`` columns
    v_pool   (L, NB, bs, KV * Dv)
    q        (B, H, n * c)         packed like a key: kv head j's key is
                                   the columns ``k_slices[j]`` (n starts,
                                   each c wide) of a row, in q's order

so a 192-wide key needs no padding to 256 lanes: the model packs it into
whole chunks (``ray_tpu.models.mimo_v2.pack_keys``) and every slice the
kernel takes is aligned. ``layer``, ``tables`` and ``lengths`` ride as
scalar prefetch, as in the dense kernel.

The grid is (slots, blocks visited). A full layer visits every logical
block up to the slot's last (``tables.shape[1]`` steps, the ones past the
last repeat its index, so nothing more is fetched). A window layer visits
``(window - 2) // bs + 2`` blocks starting at the one that holds position
``length - window``: blocks wholly behind the window are never named by
the index map, so they are never read, and the allocator may have freed
them (their table entries are then the null block).

Online softmax as in ``decode_attention.py``; the sink joins the
denominator once, at the end: ``l += exp(sink - m)``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def blocks_in_window(window: int, block_size: int) -> int:
    """Blocks that the keys ``(p - window, p]`` can touch, for any p."""
    return (window - 2) // block_size + 2


def _first_block(length, window, block_s):
    if window is None:
        return 0
    return jnp.maximum(length - window, 0) // block_s


def _kernel(layer_ref, tables_ref, len_ref, q_ref, k_ref, v_ref, *rest,
            scale: float, block_s: int, n_visit: int, group: int, dv: int,
            chunk: int, k_slices, window: Optional[int], has_sink: bool):
    del layer_ref, tables_ref            # used by the index maps only
    if has_sink:
        sink_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    blk = _first_block(length, window, block_s) + ib

    @pl.when(blk * block_s < length)
    def _compute():
        for j, starts in enumerate(k_slices):   # static unroll, kv heads
            lo, hi = j * group, (j + 1) * group
            q = q_ref[0, lo:hi, :]                          # (group, n*c)
            k = jnp.concatenate(
                [k_ref[0, :, st:st + chunk] for st in starts], axis=1)
            v = v_ref[0, :, j * dv:(j + 1) * dv]            # (bs, dv)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (group, bs)
            col = blk * block_s + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            ok = col < length
            if window is not None:
                ok &= col >= length - window
            s = jnp.where(ok, s, NEG_INF)

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

    @pl.when(ib == n_visit - 1)
    def _finalize():
        l = l_ref[:, :1]
        if has_sink:
            l = l + jnp.exp(sink_ref[:, :1] - m_ref[:, :1])
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def paged_hybrid_decode_attention(
        q, k_pool, v_pool, layer, tables, lengths, *, scale: float,
        k_slices: Sequence[Tuple[int, ...]], dv: int,
        window: Optional[int] = None, sink=None,
        name: str = "paged_hybrid_decode", interpret: bool = False):
    """q (B, H, n*c) packed; k_pool (L, NB, bs, Wk); v_pool
    (L, NB, bs, KV*dv); layer () int32; tables (B, MBS) int32; lengths
    (B,) int32, the new token included; ``sink`` (H,) float32 or None.
    -> (B, H, dv) in q.dtype. ``name`` is the custom call's instruction
    name, so a trace tells a full layer's calls from a window layer's."""
    B, H, qw = q.shape
    bs = k_pool.shape[2]
    KV = len(k_slices)
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    chunk = qw // len(k_slices[0])
    n_visit = (tables.shape[1] if window is None
               else min(blocks_in_window(window, bs), tables.shape[1]))
    kernel = functools.partial(
        _kernel, scale=scale, block_s=bs, n_visit=n_visit, group=H // KV,
        dv=dv, chunk=chunk, k_slices=tuple(map(tuple, k_slices)),
        window=window, has_sink=sink is not None)

    def kv_ix(b, ib, layer_ref, tables_ref, len_ref):
        length = len_ref[b]
        last = jnp.maximum(length - 1, 0) // bs
        blk = jnp.minimum(_first_block(length, window, bs) + ib, last)
        return (layer_ref[0], tables_ref[b, blk], 0, 0)

    def row_ix(b, ib, *_):
        return (b, 0, 0)

    in_specs = [pl.BlockSpec((1, H, qw), row_ix),
                pl.BlockSpec((None, 1, bs, k_pool.shape[3]), kv_ix),
                pl.BlockSpec((None, 1, bs, v_pool.shape[3]), kv_ix)]
    args = [q, k_pool, v_pool]
    if sink is not None:
        in_specs.append(pl.BlockSpec((H, _LANES), lambda b, ib, *_: (0, 0)))
        args.append(jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None], (H, _LANES)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_visit),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, dv), row_ix),
        scratch_shapes=[
            pltpu.VMEM((H, dv), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
        ],
    )
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name=name,
        )(jnp.asarray(layer, jnp.int32).reshape(1),
          tables.astype(jnp.int32), lengths.astype(jnp.int32), *args)


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
