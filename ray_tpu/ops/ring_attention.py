"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

Each device holds a contiguous sequence chunk of q/k/v. kv chunks rotate
around the ring via ``lax.ppermute`` (nearest-neighbor ICI hop); each step
runs the local flash kernel against the visiting chunk and folds the partial
result in with a numerically-stable log-sum-exp merge. Causality is enforced
at chunk granularity (visiting chunk strictly-past → full attend, self →
causal, future → skip) so each device does only the work its rows need.

Differentiability comes for free: the merge is plain jnp and the local kernel
is the joint (out, lse) custom-vjp primitive from ``ops.attention``.

Net-new vs the reference framework — SURVEY.md §2.3 records that ring/Ulysses
/context parallelism is absent there. Also provides ``ulysses_attention``
(all-to-all seq↔heads exchange) as the lower-latency alternative when
heads % sp == 0.

Known wall-clock headroom (future rounds): striped/zigzag chunk orderings to
balance the causal triangle across ring steps.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import NEG_INF, flash_attention_with_lse


def _merge(o1, lse1, o2, lse2):
    """Combine two normalized partial attentions (o_i, lse_i) → (o, lse)."""
    m = jnp.maximum(lse1, lse2)
    m = jnp.maximum(m, NEG_INF)  # both empty → stay finite
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    l = w1 + w2
    l_safe = jnp.where(l == 0.0, 1.0, l)
    # (B, H, S) stats vs (B, S, H, D) outputs: move heads axis.
    w1o = jnp.transpose(w1 / l_safe, (0, 2, 1))[..., None]
    w2o = jnp.transpose(w2 / l_safe, (0, 2, 1))[..., None]
    o = o1 * w1o + o2 * w2o
    return o, m + jnp.log(l_safe)


def ring_attention_local(q, k, v, axis_name: str = "sp",
                         causal: bool = True,
                         scale: Optional[float] = None, block: int = 512):
    """Per-device body; call inside shard_map with q/k/v seq-sharded on
    ``axis_name``. (B, S_local, H, D) layout."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    o32 = None
    lse = None
    for step in range(n):
        if step > 0:
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)
        if step == 0:
            o_s, lse_s = flash_attention_with_lse(
                q, k, v, causal=causal, scale=scale, block=block)
            o32, lse = o_s.astype(jnp.float32), lse_s
            continue
        src = (my - step) % n  # origin of the visiting kv chunk

        def attend(q, k, v):
            o_s, lse_s = flash_attention_with_lse(
                q, k, v, causal=False, scale=scale, block=block)
            return o_s.astype(jnp.float32), lse_s

        def skip(q, k, v):
            return (jnp.zeros(q.shape, jnp.float32),
                    jnp.full((q.shape[0], q.shape[2], q.shape[1]),
                             NEG_INF, jnp.float32))

        if causal:
            o_s, lse_s = jax.lax.cond(src < my, attend, skip, q, k, v)
        else:
            o_s, lse_s = attend(q, k, v)
        o32, lse = _merge(o32, lse, o_s, lse_s)
    return o32.astype(q.dtype)


def ulysses_attention_local(q, k, v, axis_name: str = "sp",
                            causal: bool = True,
                            scale: Optional[float] = None, block: int = 512):
    """All-to-all SP: exchange seq↔heads so each device sees the full
    sequence for H/sp heads, run dense-local flash, exchange back.
    Requires heads (incl. kv heads) divisible by the axis size."""

    def seq_to_heads(x):
        # (B, S/n, H, D) → (B, S, H/n, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    from ray_tpu.ops.attention import flash_attention

    o = flash_attention(qg, kg, vg, causal=causal, scale=scale, block=block)
    return heads_to_seq(o)


def ring_attention(q, k, v, mesh, causal: bool = True,
                   scale: Optional[float] = None,
                   sp_axis: str = "sp", heads_axis: Optional[str] = "tp",
                   batch_axes: Union[str, Sequence[str]] = ("dp", "fsdp"),
                   block: int = 512, mode: str = "ring"):
    """shard_map wrapper usable inside a jitted GSPMD program.

    q/k/v: (B, S, H, D) global arrays; resharded to
    P(batch_axes, sp_axis, heads_axis, None) per device.
    ``mode``: "ring" (ppermute) or "ulysses" (all-to-all).
    """
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, sp_axis, heads_axis, None)
    local = (ring_attention_local if mode == "ring"
             else ulysses_attention_local)

    def body(q, k, v):
        return local(q, k, v, axis_name=sp_axis, causal=causal, scale=scale,
                     block=block)

    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
