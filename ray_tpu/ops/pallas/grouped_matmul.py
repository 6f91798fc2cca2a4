"""Grouped (ragged) matrix product for an expert layer (Pallas TPU):
rows sorted by expert, each tile of ``tm`` rows belonging to ONE expert,
against that expert's matrix out of a stacked (experts, K, N) tensor.

    lhs         (M, K)      rows in tiles of tm; a tile holds rows of one
                            group, padded with zero rows
    rhs         (G, K, N)   one matrix a group
    tile_group  (M / tm,)   int32, the group of each tile
    n_active    ()          int32: the leading tiles that hold rows
    -> out      (M, N)      rows of tiles at or past ``n_active`` are NOT
                            written (the caller masks them)

``M`` is static: the caller's row buffer (``models/moe.py::pass_rows``:
the worst case, every row routed here, or twice the share a chip expects,
the rows past it taking further calls; no capacity, nothing dropped); a
step usually fills a part of it.
``tile_group`` and ``n_active`` ride as scalar prefetch. The grid is
(N / tn, M / tm) with the row tiles innermost: consecutive tiles of one
expert name the same (K, tn) block of its matrix, which is then fetched
once; tiles past ``n_active`` repeat the last active tile's indices and
skip the product, so they move nothing. An expert that no row was routed
to has no tile: its weights are never read.

``tm`` is the caller's: ``models/moe.py::row_tile`` chooses it for each
program from the call's static shapes (the rows an expert expects), and
this file takes any multiple of 16. A live step loads every 128 x 128
tile of the (K, tn) block into the MXU for ``tm`` rows of work, so a
decode step, whose experts get 4-8 rows, keeps 16 and is bound by the
bytes of the experts hit; a prefill's bucket, whose experts get 16-85,
takes 32-128 so that an expert is one step a column block and not six.
At 128 rows the blocks are lhs (128, 7,168) 1.8 MB and rhs (7,168, 256)
3.7 MB, both double-buffered, beside a float32 (128, 256) output: 11.3
of the 16 MiB of scoped VMEM, the largest of the four models' calls
(``_tn`` bounds the rhs block alone: rows much wider than 7,168 would
have to narrow it, never the precision).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.util.profiling import part

NAME = "grouped_expert_matmul"
NAME_DW = "grouped_expert_matmul_dw"
_RHS_BLOCK_BYTES = 4 << 20


def _kernel(group_ref, active_ref, lhs_ref, rhs_ref, o_ref):
    del group_ref

    @pl.when(pl.program_id(1) < active_ref[0])
    def _():
        o_ref[...] = jnp.dot(
            lhs_ref[...], rhs_ref[...],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _tn(K: int, N: int, itemsize: int) -> int:
    """Widest multiple of 128 that divides N with a (K, tn) block of at
    most 4 MiB (double-buffered: half the default scoped VMEM)."""
    tn = N
    while tn > 128 and (tn * K * itemsize > _RHS_BLOCK_BYTES or N % tn):
        tn -= 128
    return tn if N % tn == 0 else N


def grouped_matmul(lhs, rhs, tile_group, n_active, *, tm: int,
                   out_dtype=None, name: str = NAME,
                   interpret: bool = False):
    """``name`` is the custom call's instruction name: a prefill's
    products carry another than a decode step's, so a trace tells them
    apart."""
    M, K = lhs.shape
    G, _, N = rhs.shape
    if M % tm:
        raise ValueError(f"rows {M} not a multiple of the tile {tm}")
    out_dtype = out_dtype or lhs.dtype
    tn = _tn(K, N, rhs.dtype.itemsize)

    def tile(i, active_ref):
        return jnp.minimum(i, jnp.maximum(active_ref[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // tn, M // tm),
        in_specs=[
            pl.BlockSpec((tm, K), lambda n, i, g, a: (tile(i, a), 0)),
            pl.BlockSpec((None, K, tn),
                         lambda n, i, g, a: (g[tile(i, a)], 0, n)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda n, i, g, a: (tile(i, a), n)),
    )
    with part(name):
        return pl.pallas_call(
            _kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name=name,
        )(tile_group.astype(jnp.int32),
          jnp.asarray(n_active, jnp.int32).reshape(1), lhs, rhs)


def grouped_matmul_reference(lhs, rhs, tile_group, n_active, *, tm: int,
                             out_dtype=None, name: str = NAME):
    """XLA path (and the kernel's oracle): each tile against a gathered
    copy of its group's matrix; inactive tiles give zeros."""
    M, K = lhs.shape
    out_dtype = out_dtype or lhs.dtype
    tiles = lhs.reshape(M // tm, tm, K)
    with part(name):
        out = jnp.einsum("itk,ikn->itn", tiles, rhs[tile_group],
                         preferred_element_type=jnp.float32)
        live = jnp.arange(M // tm) < n_active
        return jnp.where(live[:, None, None], out, 0.0).reshape(
            M, -1).astype(out_dtype)


def grouped_matmul_dw_reference(lhs, dout, tile_group, n_active, *, tm: int,
                                groups: int, out_dtype=None,
                                name: str = NAME_DW):
    """XLA path (and the ``dw`` kernel's oracle): each live tile's
    ``lhs_tile^T dout_tile`` in float32, summed into its group's matrix;
    a group with no tile gets zeros."""
    M, K = lhs.shape
    out_dtype = out_dtype or lhs.dtype
    with part(name):
        live = (jnp.arange(M // tm) < n_active)[:, None, None]
        per_tile = jnp.einsum(
            "itk,itn->ikn", lhs.reshape(M // tm, tm, K),
            jnp.where(live, dout.reshape(M // tm, tm, -1), 0),
            preferred_element_type=jnp.float32)
        return jnp.zeros((groups,) + per_tile.shape[1:], jnp.float32).at[
            tile_group].add(jnp.where(live, per_tile, 0.0)).astype(out_dtype)


def _dw_kernel(group_ref, active_ref, lhs_ref, dout_ref, o_ref, acc_ref):
    i = pl.program_id(1)
    n_active = active_ref[0]
    g = group_ref[i]
    live = i < n_active

    @pl.when(live & ((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != g)))
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(live)
    def _():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # the group's last tile writes its matrix; the block goes back to HBM
    # when the next tile names another group, or at the grid's end
    @pl.when(live & ((i == n_active - 1)
                     | (group_ref[jnp.minimum(i + 1, pl.num_programs(1) - 1)]
                        != g)))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul_dw(lhs, dout, tile_group, n_active, *, tm: int,
                      groups: int, out_dtype=None, name: str = NAME_DW,
                      interpret: bool = False):
    """The weights' gradient of :func:`grouped_matmul`: lhs (M, K) and
    dout (M, N) in the same tiles of ``tm`` rows -> (groups, K, N), group
    ``g`` the sum over its tiles of ``lhs_tile^T dout_tile``, accumulated
    in float32 in VMEM over the group's consecutive tiles and written
    once. The grid is the forward's, (N / tn, M / tm) with the row tiles
    innermost; tiles at or past ``n_active`` repeat the last live tile's
    indices and add nothing. A group that no tile names is NOT written:
    :func:`grouped_product`'s backward masks it."""
    M, K = lhs.shape
    N = dout.shape[1]
    if M % tm:
        raise ValueError(f"rows {M} not a multiple of the tile {tm}")
    out_dtype = out_dtype or lhs.dtype
    tn = _tn(K, N, 4)          # the float32 accumulator is the block that counts

    def tile(i, active_ref):
        return jnp.minimum(i, jnp.maximum(active_ref[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // tn, M // tm),
        in_specs=[
            pl.BlockSpec((tm, K), lambda n, i, g, a: (tile(i, a), 0)),
            pl.BlockSpec((tm, tn), lambda n, i, g, a: (tile(i, a), n)),
        ],
        out_specs=pl.BlockSpec((None, K, tn),
                               lambda n, i, g, a: (g[tile(i, a)], 0, n)),
        scratch_shapes=[pltpu.VMEM((K, tn), jnp.float32)],
    )
    with part(name):
        return pl.pallas_call(
            _dw_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((groups, K, N), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name=name,
        )(tile_group.astype(jnp.int32),
          jnp.asarray(n_active, jnp.int32).reshape(1), lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def grouped_product(lhs, rhs, tile_group, n_active, tm: int,
                    out_dtype=None, name: str = NAME):
    """The kernel on a TPU, its oracle elsewhere. Differentiable in
    ``lhs`` and ``rhs``: the rows' gradient is the same product over the
    same tiles against the transposed matrices, the matrices' gradient
    :func:`grouped_matmul_dw` (custom call ``grouped_expert_matmul_dw``
    whatever ``name`` is). Rows of tiles at or past ``n_active`` carry
    no gradient in either direction: what lies in them, forward or
    backward, is never read."""
    mm = grouped_matmul if attention.on_tpu() else grouped_matmul_reference
    return mm(lhs, rhs, tile_group, n_active, tm=tm, out_dtype=out_dtype,
              name=name)


def _product_fwd(lhs, rhs, tile_group, n_active, tm, out_dtype, name):
    return (grouped_product(lhs, rhs, tile_group, n_active, tm, out_dtype,
                            name), (lhs, rhs, tile_group, n_active))


def product_grads(lhs, rhs, dout, tile_group, n_active, *, tm: int,
                  name: str = NAME):
    """(``dlhs``, ``drhs``) of :func:`grouped_product` for a cotangent
    ``dout`` (M, N) in ``lhs``'s dtype: the rows' gradient is the same
    product over the same tiles against the transposed matrices, under
    the forward's ``name``; the matrices' gradient is
    :func:`grouped_matmul_dw`, zeros for a group that no live tile names
    (the kernel never writes it)."""
    dlhs = grouped_product(dout, jnp.swapaxes(rhs, 1, 2), tile_group,
                           n_active, tm, lhs.dtype, name)
    dw = (grouped_matmul_dw if attention.on_tpu()
          else grouped_matmul_dw_reference)
    drhs = dw(lhs, dout, tile_group, n_active, tm=tm, groups=rhs.shape[0],
              out_dtype=rhs.dtype)
    named = jnp.zeros(rhs.shape[0], bool).at[tile_group].max(
        jnp.arange(tile_group.shape[0]) < n_active)
    return dlhs, jnp.where(named[:, None, None], drhs, 0)


def _product_bwd(tm, out_dtype, name, res, dout):
    lhs, rhs, tile_group, n_active = res
    return (*product_grads(lhs, rhs, dout.astype(lhs.dtype), tile_group,
                           n_active, tm=tm, name=name), None, None)


grouped_product.defvjp(_product_fwd, _product_bwd)
