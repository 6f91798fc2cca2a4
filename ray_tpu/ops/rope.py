"""Rotary position embeddings (non-interleaved / llama "neox" layout).

One table per theta: a model whose layer kinds rotate at different bases
(a window layer at 1e4 beside a full layer at 1e7) builds one
``rope_frequencies`` table per kind and hands each layer its own.
``apply_rope`` rotates the first ``2 * cos.shape[-1]`` dimensions of a
head and passes the rest through, so a partial rotary factor is a table
built for the rotated width alone (``rope_frequencies(rotary_dim, ...)``);
a table as wide as the head is the full rotation it always was.

Not applied here, whatever a published ``rope_scaling`` says: linear
position scaling (``{"type": "linear", "factor": f}``, as
deepseek-coder-1.3b publishes), NTK-by-parts / YaRN, Llama-3's
wavelength-dependent scaling, dynamic NTK, and the interleaved
("GPT-J") pair layout. Positions enter unscaled at base ``theta``.
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 500000.0,
                     dtype=jnp.float32):
    """(max_seq, head_dim/2) cos/sin tables."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(x, cos, sin, positions=None):
    """x: (..., seq, heads, head_dim). cos/sin: (max_seq, rotary_dim/2),
    ``rotary_dim <= head_dim``: dimensions past it are not rotated.

    ``positions``: optional (..., seq) int array for non-contiguous positions
    (decode steps, packed sequences).
    """
    if positions is None:
        seq = x.shape[-3]
        c, s = cos[:seq], sin[:seq]                # (seq, hd/2)
        c = c[:, None, :]
        s = s[:, None, :]
    else:
        c = cos[positions][..., :, None, :]
        s = sin[positions][..., :, None, :]
    rot = 2 * cos.shape[-1]
    if rot == x.shape[-1]:
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
        return out.astype(x.dtype)
    # partial rotary: the table covers the first ``rot`` dimensions only
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = jnp.split(xr, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x[..., rot:]], axis=-1)
