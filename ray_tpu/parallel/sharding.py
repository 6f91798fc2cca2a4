"""Logical-axis sharding rules.

Model code annotates arrays with *logical* axis names ("embed", "heads",
"batch", …); a :class:`ShardingRules` table maps those to mesh axes. Swapping
the table re-lays-out the whole model (fsdp vs tp vs both) without touching
model code. This replaces the reference's per-framework process-group plumbing
(torch DDP/FSDP wiring in reference ``python/ray/train/torch/config.py``) with
a declarative, compiler-visible scheme.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

LOGICAL_AXES = (
    "batch",      # global batch            → dp + fsdp
    "seq",        # sequence (activations)  → sp
    "embed",      # model dim
    "heads",      # attention heads         → tp
    "kv_heads",   # kv heads (GQA)
    "head_dim",
    "mlp",        # ffn hidden              → tp
    "vocab",      # logits vocab            → tp
    "embed_vocab",  # embedding-table vocab dim (gather axis) → replicated
    "layers",     # scan-over-layers leading axis (never sharded)
    "expert",     # MoE experts             → ep (fsdp, sp)
    "tokens",     # flattened batch·seq (MoE routing) → dp + fsdp + sp
    "kv_seq",     # kv-cache sequence dim
    None,
)

Axis = Union[str, Tuple[str, ...], None]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis → mesh axis (or tuple of mesh axes, or None=replicate)."""

    batch: Axis = ("dp", "fsdp")
    seq: Axis = "sp"
    embed: Axis = None
    embed_fsdp: Axis = "fsdp"   # weight-matrix embed dim: sharded for ZeRO-3
    heads: Axis = "tp"
    kv_heads: Axis = "tp"
    head_dim: Axis = None
    mlp: Axis = "tp"
    vocab: Axis = "tp"
    embed_vocab: Axis = None
    layers: Axis = None
    expert: Axis = ("fsdp", "sp")
    # flattened (batch·seq) token dim: the merge of the batch and seq
    # layouts, so reshape (B,S,…)→(T,…) preserves the sharding exactly
    tokens: Axis = ("dp", "fsdp", "sp")
    kv_seq: Axis = None

    def mesh_axes(self, logical_axes: Sequence[Optional[str]]):
        out = []
        used = set()
        for ax in logical_axes:
            m = getattr(self, ax) if ax is not None else None
            # A mesh axis may appear at most once in a PartitionSpec; later
            # occurrences replicate (e.g. embed_fsdp when tp==fsdp axis reuse).
            if m is not None:
                flat = (m,) if isinstance(m, str) else tuple(m)
                flat = tuple(a for a in flat if a not in used)
                used.update(flat)
                m = None if not flat else (flat[0] if len(flat) == 1 else flat)
            out.append(m)
        return tuple(out)


# Default rule tables for common regimes.
FSDP_RULES = ShardingRules(heads=None, kv_heads=None, mlp="fsdp", vocab=None,
                           embed_fsdp="fsdp")
TP_RULES = ShardingRules(embed_fsdp=None)
FSDP_TP_RULES = ShardingRules()


def set_mesh(mesh):
    """Context manager activating ``mesh`` for jitted computations (the
    abstract mesh that ``with_logical_constraint`` reads)."""
    import jax

    return jax.set_mesh(mesh)


def logical_spec(logical_axes: Sequence[Optional[str]],
                 rules: ShardingRules):
    from jax.sharding import PartitionSpec

    return PartitionSpec(*rules.mesh_axes(logical_axes))


def logical_sharding(logical_axes, mesh, rules: ShardingRules):
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, logical_spec(logical_axes, rules))


def with_logical_constraint(x, logical_axes, rules: ShardingRules):
    """`lax.with_sharding_constraint` by logical axis names (inside jit).

    No-op when no mesh is active (single-device eager/jit use), and mesh
    axes the active mesh doesn't have are dropped — the same model code runs
    unsharded, dp-only, or fully fsdp+tp+sp without edits.
    """
    import jax

    mesh = jax.sharding.get_abstract_mesh()
    legacy_mesh = None
    if mesh.empty:
        # A legacy `with mesh:` context doesn't populate the abstract mesh;
        # honor it rather than silently dropping the constraint.
        from jax._src import mesh as mesh_lib

        legacy_mesh = mesh_lib.thread_resources.env.physical_mesh
        if legacy_mesh.empty:
            return x
        mesh = legacy_mesh
    names = set(mesh.axis_names)

    def keep(ax):
        if ax is None:
            return None
        if isinstance(ax, tuple):
            kept = tuple(a for a in ax if a in names)
            return None if not kept else (kept[0] if len(kept) == 1 else kept)
        return ax if ax in names else None

    spec = jax.sharding.PartitionSpec(
        *(keep(a) for a in rules.mesh_axes(logical_axes)))
    if legacy_mesh is not None:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(legacy_mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def shard_pytree(tree, axes_tree, mesh, rules: ShardingRules):
    """Place every leaf of ``tree`` per its logical axes in ``axes_tree``.

    ``axes_tree`` has the same structure with tuples of logical axis names
    (or None leaves = fully replicated).
    """
    import jax

    def place(axes, x):
        sh = logical_sharding(axes or (None,) * getattr(x, "ndim", 0),
                              mesh, rules)
        return jax.device_put(x, sh)

    # Map over axes_tree first so its tuple leaves are treated as leaves.
    return jax.tree.map(place, axes_tree, tree,
                        is_leaf=lambda t: t is None or isinstance(t, tuple))
