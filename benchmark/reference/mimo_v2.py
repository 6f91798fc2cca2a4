"""Plain reference of the ``mimo_v2`` language model's block, cut to the
share a configuration states: full and sliding-window attention layers
mixed by ``hybrid_layer_pattern``, a dense SwiGLU or a routed expert MLP
by ``moe_layer_freq``, RMSNorm (gain ``1 + w``, as the harness stores
every norm), untied head.

Straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, no sorting of tokens. One sequence at a time, one layer's
attention weights and ONE expert's matrices cast to float32 at a time,
so that it fits beside the system under test at the published widths.
The same interface as ``dense_decoder.py``, whose rounding helpers (the
int8 control) and ``rel_err`` it shares; it imports nothing of the
program.

The layer, from the published keys (``configs/mimo-v2.5-ep16-l7.json``
repeats them and lists what is ``assumed``):

- ``x + attn(norm(x))`` then ``x + mlp(norm(x))``, eps
  ``layernorm_epsilon``.
- Attention: ``num_attention_heads`` query heads, keys ``head_dim`` wide,
  values ``v_head_dim`` wide, scale ``head_dim ** -0.5``, causal. Rotary
  (half-split pairs) on the first ``int(partial_rotary_factor *
  head_dim)`` dimensions of q and k. ``v`` times
  ``attention_value_scale`` before the weighted sum. Full layers
  (pattern 0): ``num_key_value_heads``, ``rope_theta``. Window layers
  (pattern 1): ``swa_num_key_value_heads``, ``swa_rope_theta``, query i
  sees keys ``i - sliding_window < j <= i``, and a per-head sink logit
  joins the softmax as one more column whose probability is dropped.
- Routed MLP: ``s = sigmoid(x @ W_r)`` over ``router_width`` experts in
  float32; the ``num_experts_per_tok`` largest of ``s + b`` are chosen;
  weights ``s`` at the chosen over their sum (``norm_topk_prob``), times
  ``routed_scaling_factor`` (null: 1). THE SHARE: only the experts
  ``experts_first .. experts_first + n_routed_experts - 1`` are held;
  the others' terms are left out of the sum, as on the chip of the
  deployment the configuration states, and that partial sum goes on.

``quant`` makes the CONTROL (see ``dense_decoder.py``): every weight
matrix multiply of attention, the MLPs and the head in int8 / fp8. The
router stays in float32 in the control too: the configuration states it
so, and rounding it would fail the control for a reason of its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (F32, _act, _quantize,
                                               _rmsnorm, head, nll, rel_err)

__all__ = ["logits", "last_block_loss_and_grads", "rel_err", "nll",
           "routed_mlp", "hidden_states"]


def rotary_dim(spec: dict) -> int:
    return int(spec["partial_rotary_factor"] * spec["head_dim"])


def _rope_first(x, rot, theta):
    """x: (S, heads, D): rotate the first ``rot`` dimensions, pairs
    (x[i], x[i + rot/2]), by p * theta ** (-2 i / rot)."""
    S = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


@functools.partial(jax.jit, static_argnames=(
    "rot", "theta", "eps", "window", "value_scale", "quant"))
def attention(x, layer, *, rot, theta, eps, window, value_scale, quant=None):
    """x + attn(norm(x)) on one sequence (S, hidden) float32. ``window``
    None: a full layer; else a window layer with ``layer["sink"]``."""
    with jax.default_matmul_precision("highest"):
        w = {k: layer[k].astype(F32)
             for k in ("attn_norm", "wq", "wk", "wv", "wo")}
        wq = _quantize(w["wq"], quant, (0,))
        wk = _quantize(w["wk"], quant, (0,))
        wv = _quantize(w["wv"], quant, (0,))
        wo = _quantize(w["wo"], quant, (0, 1))
        S = x.shape[0]
        H, KV, D = wq.shape[1], wk.shape[1], wq.shape[2]
        h = _act(_rmsnorm(x, w["attn_norm"], eps), quant)
        q = _rope_first(jnp.einsum("se,ehd->shd", h, wq), rot, theta)
        k = _rope_first(jnp.einsum("se,ehd->shd", h, wk), rot, theta)
        v = jnp.einsum("se,ehd->shd", h, wv) * value_scale
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * (D ** -0.5)
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        s = jnp.where(seen[None], s, -jnp.inf)
        if window is not None:
            sink = jnp.broadcast_to(
                layer["sink"].astype(F32)[:, None, None], (H, S, 1))
            p = jax.nn.softmax(jnp.concatenate([s, sink], -1), -1)[..., :-1]
        else:
            p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v)
        return x + jnp.einsum(
            "shd,hde->se", _act(a.reshape(S, -1), quant).reshape(a.shape),
            wo)


@functools.partial(jax.jit, static_argnames=("quant",))
def swiglu(h, wg, wu, wd, *, quant=None):
    """silu(h @ wg) * (h @ wu) @ wd, float32; h already normed."""
    with jax.default_matmul_precision("highest"):
        wg = _quantize(wg.astype(F32), quant, (0,))
        wu = _quantize(wu.astype(F32), quant, (0,))
        wd = _quantize(wd.astype(F32), quant, (0,))
        h = _act(h, quant)
        return _act(jax.nn.silu(h @ wg) * (h @ wu), quant) @ wd


@functools.partial(jax.jit, static_argnames=("top_k", "scale"))
def route(h, router, bias, *, top_k, scale):
    """(S, E) float32 weights, zero off the chosen; and the share of the
    (token, expert) choices that differ when the same activations are
    first rounded to bfloat16 (near-ties flip)."""
    with jax.default_matmul_precision("highest"):
        def choose(h):
            s = jax.nn.sigmoid(h @ router.astype(F32))
            _, idx = jax.lax.top_k(s + bias.astype(F32)[None], top_k)
            chosen = jnp.zeros(s.shape, bool).at[
                jnp.arange(s.shape[0])[:, None], idx].set(True)
            return s, chosen

        s, chosen = choose(h)
        _, rounded = choose(h.astype(jnp.bfloat16).astype(F32))
        w = jnp.where(chosen, s, 0.0)
        w = w / jnp.sum(w, axis=-1, keepdims=True) * scale
        flipped = jnp.sum(chosen & ~rounded) / jnp.sum(chosen)
        return w, flipped


def routed_mlp(h, layer, spec, *, held=None, quant=None):
    """The routed MLP's partial sum over the experts ``held = (first,
    count)`` (the configuration's share by default) for normed
    activations h (S, hidden) float32; ``layer`` holds ``router``,
    ``router_bias`` and the held experts' matrices, expert by expert.
    -> (sum (S, hidden), share of choices that bf16 would flip)."""
    first, count = held or (spec.get("experts_first", 0),
                            spec["n_routed_experts"])
    scale = spec.get("routed_scaling_factor") or 1.0
    w, flipped = route(h, layer["router"], layer["router_bias"],
                       top_k=spec["num_experts_per_tok"], scale=float(scale))
    out = jnp.zeros_like(h)
    for e in range(count):
        y = swiglu(h, layer["we_gate"][e], layer["we_up"][e],
                   layer["we_down"][e], quant=quant)
        out = out + w[:, first + e, None] * y
    return out, flipped


def block(x, layer, spec, l, *, quant=None):
    """Layer ``l`` on one sequence. -> (x, flipped share or None)."""
    eps = float(spec["layernorm_epsilon"])
    window = spec["hybrid_layer_pattern"][l] == 1
    x = attention(
        x, {k: layer[k] for k in ("attn_norm", "wq", "wk", "wv", "wo",
                                  "sink") if k in layer},
        rot=rotary_dim(spec), eps=eps,
        theta=float(spec["swa_rope_theta" if window else "rope_theta"]),
        window=int(spec["sliding_window"]) if window else None,
        value_scale=float(spec["attention_value_scale"]), quant=quant)
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, layer["mlp_norm"].astype(F32), eps)
    if spec["moe_layer_freq"][l]:
        y, flipped = routed_mlp(h, layer, spec, quant=quant)
        return x + y, flipped
    return x + swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"],
                      quant=quant), None


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
                eps=float(spec["layernorm_epsilon"]), quant=quant)


def last_block_loss_and_grads(params, tokens, spec, *, quant=None):
    raise SystemExit(
        "the mimo_v2 reference has no backward pass: no train cell runs "
        "this block (at 16 bytes a parameter even its floors need 36 GB)")
