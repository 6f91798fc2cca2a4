"""Rotary position embeddings (non-interleaved / llama "neox" layout).

One table per theta: a model whose layer kinds rotate at different bases
(a window layer at 1e4 beside a full layer at 1e7) builds one
``rope_frequencies`` table per kind and hands each layer its own.
``apply_rope`` rotates the first ``2 * cos.shape[-1]`` dimensions of a
head and passes the rest through, so a partial rotary factor is a table
built for the rotated width alone (``rope_frequencies(rotary_dim, ...)``);
a table as wide as the head is the full rotation it always was.

YaRN (NTK-by-parts, ``{"type": "yarn", ...}``) is a table built with
``yarn=YarnScaling(...)``: pair i's inverse frequency is blended between
``1 / f_i`` (dimensions that turn more than ``beta_fast`` times within
the original context: kept) and ``1 / (factor * f_i)`` (fewer than
``beta_slow`` turns: interpolated) by a linear ramp over the pair
indices between, and the tables are multiplied by ``m(mscale) /
m(mscale_all_dim)`` with ``m(s) = 0.1 s ln(factor) + 1``. The softmax
scale's own factor, ``m(mscale_all_dim) ** 2``, is the caller's
(:meth:`YarnScaling.attention_factor`): it multiplies ``q . k``, not a
table.

Not applied here, whatever a published ``rope_scaling`` says: linear
position scaling (``{"type": "linear", "factor": f}``, as
deepseek-coder-1.3b publishes), Llama-3's wavelength-dependent scaling,
dynamic NTK, and the interleaved ("GPT-J") pair layout. Without
``yarn`` positions enter unscaled at base ``theta``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax.numpy as jnp

from ray_tpu.util.profiling import part


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """A published ``rope_scaling`` of type ``yarn``."""

    factor: float
    original_max_seq: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def _m(self, s: float) -> float:
        return 1.0 if self.factor <= 1.0 else \
            0.1 * s * math.log(self.factor) + 1.0

    @property
    def table_factor(self) -> float:
        """What cos and sin are multiplied by."""
        return self._m(self.mscale) / self._m(self.mscale_all_dim)

    @property
    def attention_factor(self) -> float:
        """What the softmax scale ``d ** -0.5`` is multiplied by."""
        return self._m(self.mscale_all_dim) ** 2

    def ramp_ends(self, head_dim: int, theta: float):
        """(low, high) pair indices between which the blend runs."""
        def pair_of(turns):
            return head_dim * math.log(self.original_max_seq / (
                turns * 2.0 * math.pi)) / (2.0 * math.log(theta))
        low = max(math.floor(pair_of(self.beta_fast)), 0)
        high = min(math.ceil(pair_of(self.beta_slow)), head_dim - 1)
        return low, high

    def inverse_frequencies(self, head_dim: int, theta: float):
        f = theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                      / head_dim)
        low, high = self.ramp_ends(head_dim, theta)
        ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                        / max(high - low, 0.001), 0.0, 1.0)
        return (1.0 / (self.factor * f)) * ramp + (1.0 / f) * (1.0 - ramp)


@part("attn_proj")
def rope_frequencies(head_dim: int, max_seq: int, theta: float = 500000.0,
                     dtype=jnp.float32, yarn: Optional[YarnScaling] = None):
    """(max_seq, head_dim/2) cos/sin tables."""
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                               / head_dim))
    else:
        inv = yarn.inverse_frequencies(head_dim, theta)
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    if yarn is not None and yarn.table_factor != 1.0:
        cos, sin = cos * yarn.table_factor, sin * yarn.table_factor
    return cos.astype(dtype), sin.astype(dtype)


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
