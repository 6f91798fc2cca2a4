"""Weights from ``--seed``: one jitted call, on the device, in bfloat16.

The benchmark makes the weights itself (the reference may use nothing
the program has made) and hands them to the program in the layout its
builders take: ``embed``, ``layers`` stacked on a leading axis,
``final_norm``, ``lm_head``; a norm's stored weight ``w`` scales by
``1 + w``. Imported only by a process that holds the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0):
    """A key from any whole number up to a little over 2**31 (more than a
    signed 32-bit word holds), and a stream number for independent draws."""
    seed = int(seed)
    # "rbg": the device's own bit generator, several times faster than
    # threefry for billions of draws (set-up is paid by every run)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    key = jax.random.fold_in(key, seed >> 31)
    return jax.random.fold_in(key, stream)


def shapes(spec: dict) -> dict:
    L, h, m = (spec["num_hidden_layers"], spec["hidden_size"],
               spec["intermediate_size"])
    H, KV, D = (spec["num_attention_heads"], spec["num_key_value_heads"],
                spec["head_dim"])
    out = {
        "embed": (spec["vocab_size"], h),
        "layers": {
            "attn_norm": (L, h), "wq": (L, h, H, D), "wk": (L, h, KV, D),
            "wv": (L, h, KV, D), "wo": (L, H, D, h), "mlp_norm": (L, h),
            "w_gate": (L, h, m), "w_up": (L, h, m), "w_down": (L, m, h),
        },
        "final_norm": (h,),
    }
    if not spec["tie_word_embeddings"]:
        out["lm_head"] = (h, spec["vocab_size"])
    return out


def init_fn(spec: dict):
    """``key -> params``. Normal draws at ``hidden ** -0.5``; projections
    back into the residual stream are scaled down by ``sqrt(2 L)`` so that
    activations stay of order one through the depth; norm weights are
    drawn at 0.1 so that a dropped ``1 + w`` shows."""
    std = spec["hidden_size"] ** -0.5
    out_std = std / (2 * spec["num_hidden_layers"]) ** 0.5
    stds = {"attn_norm": 0.1, "mlp_norm": 0.1, "final_norm": 0.1,
            "wo": out_std, "w_down": out_std}
    tree = shapes(spec)
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
