"""The short causal depthwise convolution of a gated-convolution mixer.

    v  (B, S, h)    the gated input
    k  (h, K)       one filter a channel, tap ``j`` the weight of
                    ``v[t - (K - 1) + j]`` (the last tap is the current
                    position's); no bias, no activation
    -> (B, S, h)    c[t] = sum_j k[:, j] * v[t - (K - 1) + j], v[< 0] = 0

``K`` shifted multiply-adds in float32, written in ``v``'s dtype: every
byte of ``v`` is read ``K`` times and nothing is multiplied on the MXU,
so the call is bound by memory and XLA fuses it into what surrounds it.
``jax.grad`` gives its backward (the same taps mirrored for ``v``, a
row sum for ``k``).
"""

from __future__ import annotations

import jax.numpy as jnp

from ray_tpu.util.profiling import part


@part("short_conv")
def short_conv(v, k):
    S, taps = v.shape[1], k.shape[1]
    padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    k = k.astype(jnp.float32)
    out = sum(padded[:, j:j + S].astype(jnp.float32) * k[:, j]
              for j in range(taps))
    return out.astype(v.dtype)
