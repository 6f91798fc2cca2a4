"""Weights from ``--seed``: one jitted call, on the device, in bfloat16.

The benchmark makes the weights itself (the reference may use nothing
the program has made) and hands them to the program in the layout its
builders take, which the block's adapter states (``weight_shapes``,
``weight_stds``). Imported only by a process that holds the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import model_spec


def seed_key(seed: int, stream: int = 0):
    """A key from any whole number up to a little over 2**31 (more than a
    signed 32-bit word holds), and a stream number for independent draws."""
    seed = int(seed)
    # "rbg": the device's own bit generator, several times faster than
    # threefry for billions of draws (set-up is paid by every run)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    key = jax.random.fold_in(key, seed >> 31)
    return jax.random.fold_in(key, stream)


def init_fn(spec: dict):
    """``key -> params``: the tree of the block's adapter
    (``weight_shapes``), each leaf a normal draw at its ``weight_stds``.
    The key is split by the flattened order of the tree."""
    arch = model_spec.adapter(spec)
    std, stds = arch.weight_stds(spec)
    tree = arch.weight_shapes(spec)
    leaves, treedef = jax.tree.flatten_with_path(
        tree, is_leaf=lambda t: isinstance(t, tuple))

    def init(key):
        keys = jax.random.split(key, len(leaves))
        vals = []
        for k, (path, shape) in zip(keys, leaves):
            name = path[-1].key
            vals.append(jax.random.normal(k, shape, jnp.bfloat16)
                        * jnp.bfloat16(stds.get(name, std)))
        return jax.tree.unflatten(treedef, vals)

    return init


def make(spec: dict, seed: int, sharding=None):
    """The whole tree in one jitted call."""
    fn = jax.jit(init_fn(spec), out_shardings=sharding)
    return fn(seed_key(seed))
