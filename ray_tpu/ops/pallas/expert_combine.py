"""The combine of an expert layer held by share (Pallas TPU): each token's
weighted sum over the pairs PLACED on this chip, read out of the down
product's row buffer where it lies.

    y_rows    (M, h) f32    the down product's rows, sorted by expert; rows
                            of tiles past ``n_active`` were never written
    row_pair  (T, k) i32    the row of each (token, expert) pair
    placed    (T, k) bool   whether the pair's expert is held here
    w         (T, k) f32    the router's weights
    -> y      (T, h) f32    y[t] = sum_j placed[t, j] ? w[t, j] *
                            y_rows[row_pair[t, j]] : 0, summed in float32
                            in the order j = 0 .. k - 1

``y_rows`` stays in HBM, and a one-row slice of a float32 buffer tiled
(8, 128) is no copy the chip's compiler takes: a placed pair's copy is
its row's aligned GROUP of 8 rows, HBM -> a ring of row groups in VMEM (a
DMA semaphore a slot). XLA first moves each token's placed pairs to the
front of its ``k``, in their order, and counts them, so the kernel's
loops run over the pairs placed and meet no other. The grid walks tiles
of 16 tokens; the copies run ahead of the sum, token after token, as far
as the ring has room for one more token's pairs (their state, the next
token to start and the copies started and taken, lives in SMEM from one
grid step to the next); the sum waits for a pair's group, takes the one
row out of it and adds ``w * row`` into the token's row of the (16, h)
output block, zeroed first. Nothing is read that no copy wrote and no
row of a pair that is not placed is ever copied: rows of tiles past
``n_active`` may hold anything. No (T, k, h) array exists: what moves is
eight rows a placed pair and the (T, h) result once, where the XLA form
(the oracle below) gathers, selects and sums ``T * k`` rows whatever
share of them is placed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention

NAME = "expert_combine"
_GROUP = 8                    # rows of a float32 tile: what one copy moves
_TOKENS = 16                  # tokens a grid step
_RING_BYTES = 6 << 20
_CALL_PAIRS = 1 << 16         # pairs a call prefetches: 576 KiB of SMEM
_GATHER_BYTES = 32 << 20      # kernel_serves: the gather's array from which
                              # the kernel wins at a quarter of the pairs


def _depth(k: int, h: int) -> int:
    """Row groups the ring holds: the power of two (a slot is a mask of
    the copies' count) that ``_RING_BYTES`` holds, and at least two
    tokens' pairs (a token's copies are started together)."""
    depth = 1 << (2 * k - 1).bit_length()
    while 2 * depth * _GROUP * h * 4 <= _RING_BYTES:
        depth *= 2
    return depth


def _kernel(count_ref, src_ref, w_ref, y_hbm, o_ref, ring, sem, state, *,
            tq: int, k: int, depth: int, tokens: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        state[0] = 0          # the next token whose copies are to start
        state[1] = 0          # copies started
        state[2] = 0          # copies taken

    def group_copy(row, n):
        first = pl.multiple_of((row // _GROUP) * _GROUP, _GROUP)
        slot = n & (depth - 1)
        return pltpu.make_async_copy(
            y_hbm.at[pl.ds(first, _GROUP), :], ring.at[slot], sem.at[slot])

    def start_token(carry):
        t, started = carry

        def pair(p, n):
            group_copy(src_ref[t * k + p], n).start()
            return n + 1
        return t + 1, jax.lax.fori_loop(0, count_ref[t], pair, started)

    o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    def token(tl, carry):
        ahead, started, taken = carry
        # copies run ahead of the sum as far as the ring has room for
        # one more token's pairs
        ahead, started = jax.lax.while_loop(
            lambda c: (c[0] < tokens) & (c[1] - taken + k <= depth),
            start_token, (ahead, started))
        t = i * tq + tl

        def pair(p, n):
            row = src_ref[t * k + p]
            group_copy(row, n).wait()
            o_ref[pl.ds(tl, 1), :] += (
                ring[n & (depth - 1), pl.ds(row % _GROUP, 1), :]
                * w_ref[t * k + p])
            return n + 1
        return ahead, started, jax.lax.fori_loop(0, count_ref[t], pair, taken)

    state[0], state[1], state[2] = jax.lax.fori_loop(
        0, tq, token, (state[0], state[1], state[2]))


def expert_combine(y_rows, row_pair, placed, w, *, interpret: bool = False):
    """See the module's text. A call prefetches its pairs' source rows
    and weights into SMEM (1 MiB a core), so more than ``_CALL_PAIRS``
    pairs (a train step's 32,768 tokens; no serving program comes near)
    are combined in runs of tokens, one call a run over the same
    ``y_rows``."""
    T, k = row_pair.shape
    M, h = y_rows.shape
    if M % _GROUP:
        raise ValueError(f"rows {M} not a multiple of the group {_GROUP}")
    run = _CALL_PAIRS // k // _TOKENS * _TOKENS
    if T > run:
        return jnp.concatenate([
            expert_combine(y_rows, row_pair[t:t + run], placed[t:t + run],
                           w[t:t + run], interpret=interpret)
            for t in range(0, T, run)])
    tq = _TOKENS
    pad = -T % tq
    if pad:                   # a tail of tokens with no placed pair
        row_pair, placed, w = (jnp.pad(a, ((0, pad), (0, 0)))
                               for a in (row_pair, placed, w))
    tokens = T + pad
    depth = _depth(k, h)
    # a token's placed pairs moved to the front of its k, in their order:
    # the kernel's loops run over the pairs placed and meet no other
    rank = jnp.cumsum(placed, axis=1, dtype=jnp.int32)     # 1 .. count
    nth = placed[:, None, :] & (rank[:, None, :]
                                == jnp.arange(1, k + 1)[None, :, None])
    src = jnp.sum(jnp.where(nth, row_pair[:, None, :], 0), axis=2)
    w = jnp.sum(jnp.where(nth, w[:, None, :], 0.0), axis=2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(tokens // tq,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tq, h), lambda i, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((depth, _GROUP, h), jnp.float32),
                        pltpu.SemaphoreType.DMA((depth,)),
                        pltpu.SMEM((3,), jnp.int32)],
    )
    y = pl.pallas_call(
        functools.partial(_kernel, tq=tq, k=k, depth=depth, tokens=tokens),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tokens, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=NAME,
    )(rank[:, -1], src.astype(jnp.int32).reshape(-1),
      w.astype(jnp.float32).reshape(-1), y_rows)
    return y[:T] if pad else y


def expert_combine_reference(y_rows, row_pair, placed, w):
    """XLA path (and the kernel's oracle): every pair's row gathered into
    (T, k, h), selected, weighed and summed."""
    y_pairs = jnp.where(
        placed[..., None],
        y_rows[jnp.minimum(row_pair, y_rows.shape[0] - 1)], 0.0)
    return jnp.sum(y_pairs * w[..., None], axis=1)


def kernel_serves(T: int, k: int, h: int, held: int, experts: int) -> bool:
    """Whether the kernel is the faster form of a call, from its static
    shapes. It copies EIGHT rows for a placed pair, so it wins wherever
    at most an eighth of the router's experts are held (12 of 192, 16 of
    256: its copies are then no more than one of the gather's three
    passes over ``T * k`` rows). Between an eighth and the whole (128 of
    512) it wins once the gather's (T, k, h) float32 array is 32 MiB or
    more (the state-space model's 512 and 1,024 buckets, 46 and 92 MB:
    0.1-0.5 ms a layer) and loses 0.03-0.07 ms a layer below (its 256
    bucket and its decode step, 23 and 17 MB). A set held whole places
    every pair: the gather moves no row in vain and the kernel would
    move eight times as many (0.76 against 0.37 ms at 1,024 x 8 x
    2,048). My chip runs, PR 50: PERF.md section 6."""
    if held >= experts:
        return False
    return _GROUP * held <= experts or T * k * h * 4 >= _GATHER_BYTES


def combine(y_rows, row_pair, placed, w, *, held: int, experts: int):
    """The kernel on a TPU where :func:`kernel_serves` the call (a share
    of the router's ``experts`` is ``held``: most pairs are placed
    elsewhere and the kernel moves only those placed here); its oracle
    elsewhere. Its transpose is part of the expert layer's backward
    (``models/moe.py::_down_and_combine``), which never builds the
    float32 gradient of ``y_rows``."""
    if attention.on_tpu() and kernel_serves(
            *row_pair.shape, y_rows.shape[1], held, experts):
        return expert_combine(y_rows, row_pair, placed, w)
    return expert_combine_reference(y_rows, row_pair, placed, w)
