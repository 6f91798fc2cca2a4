"""Plain reference of the ``laguna`` language model's block: full and
sliding-window attention layers mixed by ``layer_types``, query heads by
layer (``num_attention_heads_per_layer``) over one count of KV heads, a
per-head sigmoid gate on the attention output, a dense SwiGLU or
``shared(x) + routed(x)`` by ``mlp_layer_types``, RMSNorm (gain ``1 +
w``, as the harness stores every norm), untied head.

Straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, no sorting of tokens. One sequence at a time, layer by layer,
one layer's attention weights and ONE expert's matrices cast to float32
at a time, so that 3.87 B parameters fit beside the system under test at
the published widths. The interface of ``dense_decoder.py``, whose
rounding helpers (the int8 control) and ``rel_err`` it shares; it
imports nothing of the program.

The layer, from the published keys (``configs/laguna-xs2-l5.json``
repeats them and lists what is ``assumed``):

- ``x + attn(norm(x))`` then ``x + mlp(norm(x))``, eps ``rms_norm_eps``.
- Attention of layer ``l``: ``H_l = num_attention_heads_per_layer[l]``
  query heads, ``num_key_value_heads`` KV heads, all ``head_dim`` wide;
  ``z_h[i, j] = q_h[i] . k_g(h)[j] / sqrt(head_dim)``, ``g(h) = h //
  (H_l / KV)``; causal, and on a ``sliding_attention`` layer key j
  stands for query i only where ``i - sliding_window < j <= i``; softmax;
  ``o_h = sum_j p v``; then the gate ``o_h <- sigmoid(x W_g)_h o_h`` with
  ``W_g`` (hidden, H_l) on the same normed x; output projection from
  ``H_l x head_dim``.
- Rope (half-split pairs) by the layer type's entry of
  ``rope_parameters``: the first ``partial_rotary_factor * head_dim``
  dimensions at ``rope_theta``. ``rope_type: yarn``: pair i's inverse
  frequency is ``(1 - r_i) / f_i + r_i / (factor f_i)``, ``f_i = theta
  ** (2 i / d)`` with d the rotated width, ``r`` the linear ramp from 0
  at pair ``floor(c(beta_fast))`` to 1 at pair ``ceil(c(beta_slow))``,
  ``c(b) = d ln(original_max / (2 pi b)) / (2 ln theta)``; cos and sin
  are multiplied by the published ``attention_factor`` (``0.1 ln(factor)
  + 1`` where none is given) and the softmax scale stays ``head_dim **
  -0.5``.
- Routed MLP: ``p = softmax(x @ W_r)`` over ``router_width`` (else
  ``num_experts``) experts in float32; the ``num_experts_per_tok``
  largest are chosen; weights ``p`` at the chosen over their sum, times
  ``moe_routed_scaling_factor``, on each expert's OUTPUT
  (``moe_apply_router_weight_on_input`` false); each expert a SwiGLU
  ``moe_intermediate_size`` wide. The shared expert
  (``shared_expert_intermediate_size``) takes every token and is not
  scaled. THE SHARE, where a configuration holds one: only the experts
  ``experts_first .. experts_first + num_experts - 1`` are held and the
  others' terms are left out of the sum; the configuration the
  benchmark runs holds every expert, so its layer is whole.

``quant`` makes the CONTROL (see ``dense_decoder.py``): every weight
matrix multiply of attention (the gate's among them), the MLPs and the
head in int8 / fp8. The router stays in float32 in the control too: the
configuration states it so, and rounding it would fail the control for a
reason of its own.
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
           "routed_mlp", "shared_mlp", "hidden_states", "rope_of"]

ATTENTION_KEYS = ("attn_norm", "wq", "wk", "wv", "w_out_gate", "wo")


def rope_of(spec: dict, layer_type: str):
    """(inverse frequencies (rot/2,), the tables' factor) of a layer
    type's ``rope_parameters`` entry."""
    rp = spec["rope_parameters"][layer_type]
    d = int(rp["partial_rotary_factor"] * spec["head_dim"])
    theta = float(rp["rope_theta"])
    f = theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    if rp["rope_type"] == "default":
        return 1.0 / f, 1.0
    if rp["rope_type"] != "yarn":
        raise SystemExit(f"rope_type {rp['rope_type']!r}: the laguna "
                         "reference knows default and yarn")
    factor = float(rp["factor"])

    def pair_of(turns):
        return d * math.log(rp["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(rp["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rp["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv = (1.0 - ramp) / f + ramp / (factor * f)
    return inv, float(rp.get("attention_factor",
                             0.1 * math.log(factor) + 1.0))


def _rope_first(x, inv, table_factor):
    """x: (S, heads, D): position p rotates the pair (x[i], x[i + r/2])
    of the first ``r = 2 len(inv)`` dimensions by p * inv[i]; the others
    pass through."""
    r = 2 * inv.shape[0]
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * table_factor)[:, None, :]
    sin = (jnp.sin(ang) * table_factor)[:, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


@functools.partial(jax.jit, static_argnames=("table_factor", "eps",
                                             "window", "quant"))
def attention(x, layer, inv, *, table_factor, eps, window, quant=None):
    """x + attn(norm(x)) on one sequence (S, hidden) float32. ``window``
    None: a full layer."""
    with jax.default_matmul_precision("highest"):
        w = {k: layer[k].astype(F32) for k in ATTENTION_KEYS}
        wq = _quantize(w["wq"], quant, (0,))
        wk = _quantize(w["wk"], quant, (0,))
        wv = _quantize(w["wv"], quant, (0,))
        wg = _quantize(w["w_out_gate"], quant, (0,))
        wo = _quantize(w["wo"], quant, (0, 1))
        S = x.shape[0]
        H, KV, D = wq.shape[1], wk.shape[1], wq.shape[2]
        h = _act(_rmsnorm(x, w["attn_norm"], eps), quant)
        q = _rope_first(jnp.einsum("se,ehd->shd", h, wq), inv, table_factor)
        k = _rope_first(jnp.einsum("se,ehd->shd", h, wk), inv, table_factor)
        v = jnp.einsum("se,ehd->shd", h, wv)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * (D ** -0.5)
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v)
        a = a * jax.nn.sigmoid(h @ wg)[:, :, None]           # the gate
        return x + jnp.einsum(
            "shd,hde->se", _act(a.reshape(S, -1), quant).reshape(a.shape),
            wo)


@functools.partial(jax.jit, static_argnames=("top_k", "scale"))
def route(h, router, *, top_k, scale):
    """(S, E) float32 weights, zero off the chosen; and the share of the
    (token, expert) choices that differ when the same activations are
    first rounded to bfloat16 (near-ties flip)."""
    with jax.default_matmul_precision("highest"):
        def choose(h):
            p = jax.nn.softmax(h @ router.astype(F32), axis=-1)
            _, idx = jax.lax.top_k(p, top_k)
            chosen = jnp.zeros(p.shape, bool).at[
                jnp.arange(p.shape[0])[:, None], idx].set(True)
            return p, chosen

        p, chosen = choose(h)
        _, rounded = choose(h.astype(jnp.bfloat16).astype(F32))
        w = jnp.where(chosen, p, 0.0)
        w = w / jnp.sum(w, axis=-1, keepdims=True) * scale
        return w, jnp.sum(chosen & ~rounded) / jnp.sum(chosen)


def routed_mlp(h, layer, spec, *, held=None, quant=None):
    """The routed MLP's sum over the experts ``held = (first, count)``
    (the configuration's by default: all of them where it holds every
    expert) for normed activations h (S, hidden) float32; ``layer`` holds
    ``router`` and the held experts' matrices, expert by expert. -> (sum
    (S, hidden), share of choices that bf16 would flip)."""
    first, count = held or (spec.get("experts_first", 0),
                            spec["num_experts"])
    w, flipped = route(h, layer["router"],
                       top_k=spec["num_experts_per_tok"],
                       scale=float(spec["moe_routed_scaling_factor"]))
    out = jnp.zeros_like(h)
    for e in range(count):
        y = swiglu(h, layer["we_gate"][e], layer["we_up"][e],
                   layer["we_down"][e], quant=quant)
        out = out + w[:, first + e, None] * y
    return out, flipped


def shared_mlp(h, layer, *, quant=None):
    return swiglu(h, layer["ws_gate"], layer["ws_up"], layer["ws_down"],
                  quant=quant)


def block(x, layer, spec, l, *, quant=None):
    """Layer ``l`` on one sequence. -> (x, flipped share or None)."""
    eps = float(spec["rms_norm_eps"])
    layer_type = spec["layer_types"][l]
    inv, table_factor = rope_of(spec, layer_type)
    x = attention(
        x, {k: layer[k] for k in ATTENTION_KEYS}, inv,
        table_factor=table_factor, eps=eps, quant=quant,
        window=(int(spec["sliding_window"])
                if layer_type == "sliding_attention" else None))
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, layer["mlp_norm"].astype(F32), eps)
    if spec["mlp_layer_types"][l] == "dense":
        return x + swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"],
                          quant=quant), None
    y, flipped = routed_mlp(h, layer, spec, quant=quant)
    return x + shared_mlp(h, layer, quant=quant) + y, flipped


def hidden_states(params, tokens, spec, *, quant=None, upto=None):
    n = spec["num_hidden_layers"] if upto is None else upto
    x = params["embed"][tokens].astype(F32)
    flips = []
    for l in range(n):
        x, flipped = block(x, params["layers"][l], spec, l, quant=quant)
        if flipped is not None:
            flips.append(round(float(flipped), 5))
    if quant is None and flips:
        # read, not judged: what bfloat16 activations do to the choices
        print(f"read router_choices_flipped_by_bf16_activations: share by "
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
        "the laguna reference has no backward pass: no train cell runs "
        "this block (at 16 bytes a parameter it fits only as one of 8 "
        "chips that share each layer)")
