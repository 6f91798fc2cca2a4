"""Plain reference of the ``axk1`` language model's block, cut to the
share a configuration states: latent (multi-head latent) attention in
every layer, a dense SwiGLU in the first ``first_k_dense_replace``
layers and ``shared(x) + routed(x)`` in the others, RMSNorm (gain ``1 +
w``, as the harness stores every norm), untied head.

Straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``: EXPANDED attention only
(keys and values of every token are made from its latent; nothing is
absorbed), no kernel, no cache, no batching, no sorting of tokens. One
sequence at a time, layer by layer, ONE expert's matrices cast to
float32 at a time, so that it fits beside the system under test at the
published widths. The interface of ``dense_decoder.py``, whose rounding
helpers (the int8 control) and ``rel_err`` it shares; it imports nothing
of the program.

The layer, from the published keys (``configs/axk1-ep16-l5.json``
repeats them and lists what is ``assumed``):

- ``x + attn(norm(x))`` then ``x + mlp(norm(x))``, eps ``rms_norm_eps``.
- Attention, ``H = num_attention_heads`` heads:
  ``c_q = rmsnorm(x W_qa)`` (``q_lora_rank`` wide, its own norm weight);
  ``q_h = c_q W_qb`` -> ``qk_nope_head_dim + qk_rope_head_dim``;
  ``[c_kv ; k_r] = x W_kva`` (``kv_lora_rank + qk_rope_head_dim``);
  ``c_kv = rmsnorm(c_kv)``; ``k_rope = rope(k_r)``, ONE row shared by
  all heads; ``q_rope = rope(q_rope)``; ``k_nope_h = c_kv W_kvb_k[h]``,
  ``v_h = c_kv W_kvb_v[h]`` (``v_head_dim``); ``z_h[i, j] = scale *
  (q_nope_h[i] . k_nope_h[j] + q_rope_h[i] . k_rope[j])``, causal
  softmax, ``o_h = sum_j p v_h[j]``, output projection from ``H x
  v_head_dim``.
- Rope on ``qk_rope_head_dim`` dimensions (half-split pairs) at
  ``rope_theta`` with YaRN (``rope_scaling``): pair i's inverse frequency
  is ``(1 - r_i) / f_i + r_i / (factor f_i)``, ``f_i = theta ** (2 i /
  d)``, ``r`` the linear ramp from 0 at pair ``floor(c(beta_fast))`` to 1
  at pair ``ceil(c(beta_slow))``, ``c(b) = d ln(original_max / (2 pi b))
  / (2 ln theta)``; the tables are multiplied by ``m(mscale) /
  m(mscale_all_dim)`` and ``scale = (nope + rope) ** -0.5 *
  m(mscale_all_dim) ** 2`` with ``m(s) = 0.1 s ln(factor) + 1``.
- Routed MLP: ``s = sigmoid(x @ W_r)`` over ``router_width`` experts in
  float32, in ``n_group`` runs; a group's score is the sum of its two
  largest ``s``; the ``topk_group`` best groups are kept; the
  ``num_experts_per_tok`` largest ``s`` among their experts are chosen;
  weights ``s`` at the chosen over their sum (``norm_topk_prob``), times
  ``routed_scaling_factor``. No correction bias (``topk_method: none``).
  THE SHARE: only the experts ``experts_first .. experts_first +
  n_routed_experts - 1`` are held; the others' terms are left out of the
  sum, as on the chip of the deployment the configuration states. The
  shared expert (``n_shared_experts * moe_intermediate_size`` wide) is
  whole: every chip computes it alike.

``quant`` makes the CONTROL (see ``dense_decoder.py``): every weight
matrix multiply of attention, the MLPs and the head in int8 / fp8. The
router stays in float32 in the control too: the configuration states it
so, and rounding it would fail the control for a reason of its own.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (F32, _act, _quantize,
                                               _rmsnorm, head, nll, rel_err)
from benchmark.reference.mimo_v2 import swiglu      # one SwiGLU, float32

__all__ = ["logits", "last_block_loss_and_grads", "rel_err", "nll",
           "routed_mlp", "shared_mlp", "hidden_states", "yarn"]


def yarn(spec: dict):
    """(inverse frequencies (rope/2,), the tables' factor, the softmax
    scale) from ``rope_theta`` and ``rope_scaling``."""
    d, theta = spec["qk_rope_head_dim"], float(spec["rope_theta"])
    width = spec["qk_nope_head_dim"] + d
    f = theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    rs = spec.get("rope_scaling")
    if not rs:
        return 1.0 / f, 1.0, width ** -0.5
    if rs["type"] != "yarn":
        raise SystemExit(f"rope_scaling type {rs['type']!r}: the axk1 "
                         "reference knows yarn")
    factor = float(rs["factor"])

    def pair_of(turns):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0

    low = max(math.floor(pair_of(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rs["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv = (1.0 - ramp) / f + ramp / (factor * f)
    return (inv, m(rs["mscale"]) / m(rs["mscale_all_dim"]),
            width ** -0.5 * m(rs["mscale_all_dim"]) ** 2)


def _rope(x, inv, table_factor):
    """x: (S, heads, d): position p rotates the pair (x[i], x[i + d/2])
    by p * inv[i]."""
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * table_factor)[:, None, :]
    sin = (jnp.sin(ang) * table_factor)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=(
    "rank", "nope", "eps", "table_factor", "scale", "quant"))
def attention(x, layer, inv, *, rank, nope, eps, table_factor, scale,
              quant=None):
    """x + attn(norm(x)) on one sequence (S, hidden) float32, expanded."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in layer.items()}
        w_qa = _quantize(w["w_qa"], quant, (0,))
        w_qb = _quantize(w["w_qb"], quant, (0,))
        w_kva = _quantize(w["w_kva"], quant, (0,))
        w_k = _quantize(w["w_kvb_k"], quant, (0,))
        w_v = _quantize(w["w_kvb_v"], quant, (0,))
        wo = _quantize(w["wo"], quant, (0, 1))
        S = x.shape[0]
        h = _act(_rmsnorm(x, w["attn_norm"], eps), quant)
        c_q = _act(_rmsnorm(h @ w_qa, w["q_norm"], eps), quant)
        q = jnp.einsum("sr,rhd->shd", c_q, w_qb)
        kv = h @ w_kva
        c_kv = _act(_rmsnorm(kv[:, :rank], w["kv_norm"], eps), quant)
        k_rope = _rope(kv[:, None, rank:], inv, table_factor)    # (S, 1, d)
        q_rope = _rope(q[..., nope:], inv, table_factor)
        k_nope = jnp.einsum("sr,rhd->shd", c_kv, w_k)
        v = jnp.einsum("sr,rhd->shd", c_kv, w_v)
        z = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_nope)
             + jnp.einsum("qhd,kd->hqk", q_rope, k_rope[:, 0])) * scale
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where((j <= i)[None], z, -jnp.inf), axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v)
        return x + jnp.einsum(
            "shd,hde->se", _act(a.reshape(S, -1), quant).reshape(a.shape),
            wo)


@functools.partial(jax.jit, static_argnames=("top_k", "n_group",
                                             "topk_group", "scale"))
def route(h, router, *, top_k, n_group, topk_group, scale):
    """(S, E) float32 weights, zero off the chosen; and the shares of
    the (token, expert) choices and of the (token, group) choices that
    differ when the same activations are first rounded to bfloat16."""
    with jax.default_matmul_precision("highest"):
        def choose(h):
            s = jax.nn.sigmoid(h @ router.astype(F32))
            S, E = s.shape
            grouped = s.reshape(S, n_group, E // n_group)
            score = jnp.sum(jax.lax.top_k(grouped, min(
                2, E // n_group))[0], axis=-1)
            _, best = jax.lax.top_k(score, topk_group)
            kept = jnp.zeros((S, n_group), bool).at[
                jnp.arange(S)[:, None], best].set(True)
            masked = jnp.where(kept[:, :, None], grouped,
                               -jnp.inf).reshape(S, E)
            _, idx = jax.lax.top_k(masked, top_k)
            chosen = jnp.zeros((S, E), bool).at[
                jnp.arange(S)[:, None], idx].set(True)
            return s, chosen, kept

        s, chosen, kept = choose(h)
        _, chosen_r, kept_r = choose(h.astype(jnp.bfloat16).astype(F32))
        w = jnp.where(chosen, s, 0.0)
        w = w / jnp.sum(w, axis=-1, keepdims=True) * scale
        return (w, jnp.sum(chosen & ~chosen_r) / jnp.sum(chosen),
                jnp.sum(kept & ~kept_r) / jnp.sum(kept))


def routed_mlp(h, layer, spec, *, held=None, quant=None):
    """The routed MLP's partial sum over the experts ``held = (first,
    count)`` (the configuration's share by default) for normed
    activations h (S, hidden) float32; ``layer`` holds ``router`` and the
    held experts' matrices, expert by expert. -> (sum (S, hidden), share
    of expert choices, share of group choices that bf16 would flip)."""
    first, count = held or (spec.get("experts_first", 0),
                            spec["n_routed_experts"])
    w, flipped, flipped_groups = route(
        h, layer["router"], top_k=spec["num_experts_per_tok"],
        n_group=spec["n_group"], topk_group=spec["topk_group"],
        scale=float(spec.get("routed_scaling_factor") or 1.0))
    out = jnp.zeros_like(h)
    for e in range(count):
        y = swiglu(h, layer["we_gate"][e], layer["we_up"][e],
                   layer["we_down"][e], quant=quant)
        out = out + w[:, first + e, None] * y
    return out, flipped, flipped_groups


def shared_mlp(h, layer, *, quant=None):
    return swiglu(h, layer["ws_gate"], layer["ws_up"], layer["ws_down"],
                  quant=quant)


ATTENTION_KEYS = ("attn_norm", "w_qa", "q_norm", "w_qb", "w_kva", "kv_norm",
                  "w_kvb_k", "w_kvb_v", "wo")


def block(x, layer, spec, l, *, quant=None):
    """Layer ``l`` on one sequence. -> (x, (flipped expert choices,
    flipped group choices) or None)."""
    eps = float(spec["rms_norm_eps"])
    inv, table_factor, scale = yarn(spec)
    x = attention(x, {k: layer[k] for k in ATTENTION_KEYS}, inv,
                  rank=spec["kv_lora_rank"], nope=spec["qk_nope_head_dim"],
                  eps=eps, table_factor=float(table_factor),
                  scale=float(scale), quant=quant)
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, layer["mlp_norm"].astype(F32), eps)
    if l < spec["first_k_dense_replace"]:
        return x + swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"],
                          quant=quant), None
    y, flipped, flipped_groups = routed_mlp(h, layer, spec, quant=quant)
    return x + shared_mlp(h, layer, quant=quant) + y, (flipped,
                                                       flipped_groups)


def hidden_states(params, tokens, spec, *, quant=None, upto=None):
    n = spec["num_hidden_layers"] if upto is None else upto
    x = params["embed"][tokens].astype(F32)
    flips = []
    for l in range(n):
        x, flipped = block(x, params["layers"][l], spec, l, quant=quant)
        if flipped is not None:
            flips.append([round(float(f), 5) for f in flipped])
    if quant is None and flips:
        # read, not judged: what bfloat16 activations do to the choices
        print("read router_choices_flipped_by_bf16_activations: [share of "
              "(token, expert) choices, share of (token, group) choices] by "
              f"routed layer {flips}", flush=True)
    return x


def logits(params, tokens, spec, rows=None, *, quant=None):
    """Logits (rows, vocab) float32 of one sequence; ``rows`` picks
    positions (all by default)."""
    x = hidden_states(params, tokens, spec, quant=quant)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(x, params["final_norm"], params["lm_head"],
                eps=float(spec["rms_norm_eps"]), quant=quant)


def last_block_loss_and_grads(params, tokens, spec, *, quant=None):
    raise SystemExit(
        "the axk1 reference has no backward pass: no train cell runs this "
        "block (at 16 bytes a parameter even its floors need 44.6 GB)")
