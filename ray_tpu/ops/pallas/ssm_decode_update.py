"""One decode step of a Mamba-2 layer's recurrence, in place, for the
slots that are running (Pallas TPU):

    S <- exp(dt A) S + (dt x) (outer) B        a (P, N) matrix a head
    y  = S C                                    (``D x`` is the caller's)

The state of EVERY state-space layer is one float32 array, donated by
the decode step and aliased in and out of each layer's call; the layer
is a static index in the block maps, so no layer's slice is ever made.

Layout (what makes the update lane-dense and free of any relayout): a
layer's heads come in ``G`` groups that share ``B`` and ``C``; a group's
state is one (N, W) tile, ``N`` state columns down the sublanes and
``W = heads a group x head_dim`` across the lanes, head after head::

    state   (L, slots, G, N, W) float32
    xdt     (slots, G, W) float32     dt_h x_h[p] at lane h P + p
    decay   (slots, G, W) float32     exp(dt_h A_h), repeated over p
    b, c    (slots, G, N) float32
    active  (slots,) bool
    -> state, y (slots, G, W) float32 (zeros for a slot that is not
       running, whose state is untouched bit for bit)

so ``decay`` and ``xdt`` are rows, ``B`` and ``C`` columns (made from
rows by a product with the identity, the one layout change, on the idle
MXU), and ``y`` is a sum down the sublanes. The grid is (slots, G /
groups a step) over the RUNNING slots, compacted to the front through
scalar prefetch; steps past the last running slot repeat its last
block's indices and compute nothing, so they move nothing. Bound by
bytes: a slot's state is read and written once (8.4 MB a layer at 128
heads of 64 x 128) for half an operation a byte.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.util.profiling import part

NAME = "ssm_decode_update"
_BC_ROWS = 8                  # b, c and six rows of padding: one f32 tile
# groups a grid step (fewer where they do not divide the layer's): at 192
# slots of 8 groups 1, 2 and 4 all take 2.51-2.52 ms with every slot
# running, and 1.32 / 1.23 / 1.18 ms with 86 running (my chip run, PR 39:
# a step that is skipped still costs its turn)
_GROUPS_PER_STEP = 4


def _kernel(ids_ref, n_ref, s_ref, rows_ref, bc_ref, o_ref, y_ref, *,
            groups: int):
    i, g = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]

    @pl.when(i < n)
    def _():
        N = s_ref.shape[-2]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)
               ).astype(jnp.float32)
        for j in range(groups):
            rows = rows_ref[0, j]                         # (2, W)
            # (N, 8): column 0 is B, column 1 is C; exact, whatever the
            # MXU's passes: every product is with 1 or 0
            cols = jax.lax.dot_general(
                eye, bc_ref[0, j], (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            new = s_ref[0, j] * rows[1:2] + cols[:, 0:1] * rows[0:1]
            o_ref[0, j] = new
            y_ref[0, j] = jnp.sum(new * cols[:, 1:2], axis=0, keepdims=True)

    # no slot runs: every step names slot ids[0]'s last block, which is
    # written back once at the end; it has to hold what it held
    @pl.when((n == 0) & (i == 0) & (g == 0))
    def _():
        o_ref[...] = s_ref[...]


def ssm_decode_update(state, layer: int, xdt, decay, b, c, active, *,
                      interpret: bool = False):
    """See the module's text. ``layer`` is a Python int."""
    L, S, G, N, W = state.shape
    gb = math.gcd(G, _GROUPS_PER_STEP)
    steps = G // gb
    ids = jnp.argsort(~active, stable=True).astype(jnp.int32)
    n = jnp.sum(active).astype(jnp.int32).reshape(1)
    rows = jnp.stack([xdt, decay], axis=2).astype(jnp.float32)
    bc = jnp.concatenate(
        [b[:, :, None], c[:, :, None],
         jnp.zeros((S, G, _BC_ROWS - 2, N), jnp.float32)],
        axis=2).astype(jnp.float32)

    def at(i, g, ids_ref, n_ref):
        live = i < n_ref[0]
        slot = ids_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))]
        return slot, jnp.where(live, g, steps - 1)

    def state_ix(i, g, ids_ref, n_ref):
        return (layer, *at(i, g, ids_ref, n_ref), 0, 0)

    def slot_ix(i, g, ids_ref, n_ref):
        return (*at(i, g, ids_ref, n_ref), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, steps),
        in_specs=[pl.BlockSpec((None, 1, gb, N, W), state_ix),
                  pl.BlockSpec((1, gb, 2, W), slot_ix),
                  pl.BlockSpec((1, gb, _BC_ROWS, N), slot_ix)],
        out_specs=[pl.BlockSpec((None, 1, gb, N, W), state_ix),
                   pl.BlockSpec((1, gb, 1, W), slot_ix)],
    )
    with part("ssm_update"):
        state, y = pl.pallas_call(
            functools.partial(_kernel, groups=gb),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct((S, G, 1, W), jnp.float32)],
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name=NAME,
        )(ids, n, state, rows, bc)
        # no step visits a slot that is not running: nothing wrote its row
        y = jnp.where(active[:, None, None], y[:, :, 0], 0.0)
    return state, y


@part("ssm_update")
def ssm_decode_update_reference(state, layer: int, xdt, decay, b, c, active):
    """The same arithmetic in ``jax.numpy`` (the path off the TPU, and
    the kernel's oracle)."""
    old = state[layer]                                    # (S, G, N, W)
    new = (old * decay[:, :, None, :].astype(jnp.float32)
           + b[..., None].astype(jnp.float32)
           * xdt[:, :, None, :].astype(jnp.float32))
    live = active[:, None, None, None]
    new = jnp.where(live, new, old)
    y = jnp.sum(new * c[..., None].astype(jnp.float32), axis=2)
    return (state.at[layer].set(new),
            jnp.where(active[:, None, None], y, 0.0))


def ssm_decode(state, layer: int, xdt, decay, b, c, active):
    """The kernel on a TPU, its oracle elsewhere."""
    update = (ssm_decode_update if attention.on_tpu()
              else ssm_decode_update_reference)
    return update(state, layer, xdt, decay, b, c, active)
