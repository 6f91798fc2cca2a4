"""Plain reference of the ``lfm2_moe`` language model on the train path:
one mixer a layer (a gated short convolution, or grouped-query attention
with per-head q/k norms before the rotary), then a SwiGLU that is dense
in the leading layers and routed after them, a tied head, and the loss
with its gradients.

Straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``: no kernel, no sorting of
tokens, no row buffer, no scan. One sequence at a time, layer by layer.
The interface of ``dense_decoder.py``, whose rounding helpers (the int8
control), ``nll`` and ``rel_err`` it shares; it imports nothing of the
program.

The layer, for a stream ``x`` (S, hidden) (ISSUE 57's equations;
``configs/lfm2-24b-a2b-ep4-l5.json`` repeats the keys and lists what is
``assumed``):

- ``u = rmsnorm(x, op_norm)``; ``x = x + mixer(u)``; ``y = rmsnorm(x,
  ffn_norm)``; ``x = x + ffn(y)``. Every norm's gain is ``1 + w``, as
  the harness stores every norm; eps ``norm_eps``; no bias anywhere.
- conv: ``[B | C | z] = u W_in`` split IN THIS ORDER; ``v = B * z``;
  ``c_t = sum_{j=0..2} k[:, j] * v_{t-2+j}`` with ``v_{<0} = 0`` (three
  shifted adds; no activation); ``(C * c) W_out``.
- attention: ``q, k, v = u W_q, u W_k, u W_v`` as heads of
  ``hidden / heads``; ``q = rmsnorm(q, q_norm)``, ``k = rmsnorm(k,
  k_norm)`` over the head's width, one vector each shared by the heads,
  BEFORE the rotary (half-split pairs, the whole head, ``rope_theta``,
  position = index); causal ``softmax(q k^T / sqrt(D)) v``, query head i
  against KV head ``i // (heads / kv_heads)``, the scores computed in
  blocks of ``ROW_BLOCK`` query rows so that 8,192 positions fit; ``W_o``.
- dense FFN (the leading ``num_dense_layers``): ``W_down(silu(W_gate y)
  * (W_up y))``.
- routed FFN: ``s = sigmoid(y W_r)`` in float32 over ``router_width``
  experts; ``S`` = the ``num_experts_per_tok`` largest of ``s + b`` (``b``
  chooses only: a constant here); ``w_e = s_e / (sum_{S} s + 1e-6) *
  routed_scaling_factor``; ``sum_{e in S, e held} w_e expert_e(y)``: a
  plain loop over the experts HELD (``experts_first`` ..
  ``experts_first + num_experts``) with the weights as a mask; what the
  absent experts would add is left out.
- ``logits = rmsnorm(x_L, final_norm) E^T`` over the rows of the
  vocabulary held; mean next-token cross entropy.

``quant`` makes the CONTROL (see ``dense_decoder.py``): every weight
matrix multiply of the mixers, the experts, the dense FFN and the head in
int8 / fp8. The router stays in float32 in the control too, as in the
other routed references (rounding it would fail the control for a reason
of its own), and the convolution's taps are no matrix multiply.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (F32, _act, _quantize,
                                               _rmsnorm, _rope, nll,
                                               rel_err)
from benchmark.reference.mimo_v2 import swiglu      # one SwiGLU, float32

__all__ = ["logits", "loss", "loss_and_grads", "last_block_loss_and_grads",
           "rel_err", "nll", "hidden_states", "routed_ffn", "block"]

ROW_BLOCK = 1024            # query rows whose scores exist at a time
ROUTE_NORM_EPS = 1e-6


def layer_kinds(spec: dict) -> list:
    """(mixer, routed) of each layer run: ``num_hidden_layers`` of the
    file's ``layer_types`` from ``layer_first`` on, the first
    ``num_dense_layers`` of them dense."""
    first = spec.get("layer_first", 0)
    return [(kind, i >= spec["num_dense_layers"])
            for i, kind in enumerate(spec["layer_types"][
                first:first + spec["num_hidden_layers"]])]


def experts_held(spec: dict) -> tuple:
    return spec.get("experts_first", 0), spec["num_experts"]


# ------------------------------------------------------------------ mixers
def short_conv(v, k):
    """v (S, h), k (h, taps): tap j weighs ``v[t - (taps - 1) + j]``,
    zeros before the sequence."""
    S, taps = v.shape[0], k.shape[1]
    out = jnp.zeros_like(v)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros((back, v.shape[1]), v.dtype), v[:S - back]])
        out = out + shifted * k[:, j][None, :]
    return out


@functools.partial(jax.jit, static_argnames=("quant", "order"))
def conv_mixer(u, w_in, conv_k, w_out, *, quant=None, order="BCz"):
    """``order`` permutes the split for the test that holds it."""
    with jax.default_matmul_precision("highest"):
        w_in = _quantize(w_in.astype(F32), quant, (0,))
        w_out = _quantize(w_out.astype(F32), quant, (0,))
        parts = dict(zip(order, jnp.split(_act(u, quant) @ w_in, 3, -1)))
        c = short_conv(parts["B"] * parts["z"], conv_k.astype(F32))
        return _act(parts["C"] * c, quant) @ w_out


def _attend(q, k, v):
    """Causal softmax attention, (S, H, D) float32, the scores in blocks
    of ``ROW_BLOCK`` query rows."""
    S, H, D = q.shape
    rows = []
    for r0 in range(0, S, ROW_BLOCK):
        qb = q[r0:r0 + ROW_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k) * (D ** -0.5)
        seen = (jnp.arange(S)[None, :]
                <= (r0 + jnp.arange(qb.shape[0]))[:, None])
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        rows.append(jnp.einsum("hqk,khd->qhd", p, v))
    return jnp.concatenate(rows)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "quant",
                                             "norm_first"))
def attention_mixer(u, layer, *, theta, eps, quant=None, norm_first=True):
    """``norm_first`` False rotates before the q/k norms: the order the
    test holds the program NOT to have."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in layer.items()}
        wq = _quantize(w["wq"], quant, (0,))
        wk = _quantize(w["wk"], quant, (0,))
        wv = _quantize(w["wv"], quant, (0,))
        wo = _quantize(w["wo"], quant, (0, 1))
        S = u.shape[0]
        H, KV = wq.shape[1], wk.shape[1]
        h = _act(u, quant)
        q = jnp.einsum("se,ehd->shd", h, wq)
        k = jnp.einsum("se,ehd->shd", h, wk)
        v = jnp.einsum("se,ehd->shd", h, wv)
        if norm_first:
            q = _rope(_rmsnorm(q, w["q_norm"], eps), theta)
            k = _rope(_rmsnorm(k, w["k_norm"], eps), theta)
        else:
            q = _rmsnorm(_rope(q, theta), w["q_norm"], eps)
            k = _rmsnorm(_rope(k, theta), w["k_norm"], eps)
        a = _attend(q, jnp.repeat(k, H // KV, axis=1),
                    jnp.repeat(v, H // KV, axis=1))
        return jnp.einsum(
            "shd,hde->se", _act(a.reshape(S, -1), quant).reshape(a.shape),
            wo)


# -------------------------------------------------------------------- FFNs
@functools.partial(jax.jit, static_argnames=("top_k", "scale"))
def route(y, router, bias, *, top_k, scale):
    """(S, E) float32 weights, zero off the chosen (differentiable in
    ``router`` and ``y`` through the scores and their normalisation; the
    choice is a constant); and the share of the (token, expert) choices
    that differ when ``y`` is first rounded to bfloat16."""
    with jax.default_matmul_precision("highest"):
        def choose(y):
            s = jax.nn.sigmoid(y @ router.astype(F32))
            _, idx = jax.lax.top_k(
                jax.lax.stop_gradient(s) + bias.astype(F32)[None], top_k)
            return s, jnp.zeros(s.shape, bool).at[
                jnp.arange(s.shape[0])[:, None], idx].set(True)

        s, chosen = choose(y)
        _, rounded = choose(y.astype(jnp.bfloat16).astype(F32))
        w = jnp.where(chosen, s, 0.0)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_NORM_EPS) * scale
        return w, jnp.sum(chosen & ~rounded) / jnp.sum(chosen)


def routed_ffn(y, layer, spec, *, held=None, quant=None):
    """The partial sum over the experts ``held = (first, count)`` (the
    configuration's by default) for normed ``y`` (S, hidden) float32.
    -> (sum (S, hidden), share of choices bfloat16 would flip)."""
    first, count = held or experts_held(spec)
    w, flipped = route(y, layer["router"], layer["router_bias"],
                       top_k=spec["num_experts_per_tok"],
                       scale=float(spec.get("routed_scaling_factor", 1.0)))
    out = jnp.zeros_like(y)
    for e in range(count):
        out = out + w[:, first + e, None] * swiglu(
            y, layer["we_gate"][e], layer["we_up"][e], layer["we_down"][e],
            quant=quant)
    return out, flipped


# -------------------------------------------------------------------- model
def block(x, layer, spec, i, *, quant=None):
    """Layer ``i`` on one sequence (S, hidden) float32. -> (x, flipped
    share or None)."""
    eps = float(spec["norm_eps"])
    kind, routed = layer_kinds(spec)[i]
    with jax.default_matmul_precision("highest"):
        u = _rmsnorm(x, layer["op_norm"], eps)
    if kind == "conv":
        x = x + conv_mixer(u, layer["w_in"], layer["conv_k"],
                           layer["w_out"], quant=quant)
    else:
        x = x + attention_mixer(
            u, {k: layer[k] for k in ("wq", "wk", "wv", "wo", "q_norm",
                                      "k_norm")},
            theta=float(spec["rope_parameters"]["rope_theta"]), eps=eps, quant=quant)
    with jax.default_matmul_precision("highest"):
        y = _rmsnorm(x, layer["ffn_norm"], eps)
    if routed:
        out, flipped = routed_ffn(y, layer, spec, quant=quant)
        return x + out, flipped
    return x + swiglu(y, layer["w_gate"], layer["w_up"], layer["w_down"],
                      quant=quant), None


def hidden_states(params, tokens, spec, *, quant=None, upto=None):
    n = spec["num_hidden_layers"] if upto is None else upto
    x = params["embed"][tokens].astype(F32)
    flips = []
    for i in range(n):
        x, flipped = block(x, params["layers"][i], spec, i, quant=quant)
        if flipped is not None and not isinstance(flipped, jax.core.Tracer):
            flips.append(round(float(flipped), 5))
    if quant is None and flips:
        print(f"read router_choices_flipped_by_bf16_activations: share by "
              f"routed layer {flips}", flush=True)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, final_norm, table, *, eps, quant=None):
    """Final norm, then the head ``table`` (V, hidden), the embedding's
    own rows. -> (rows, V) float32."""
    with jax.default_matmul_precision("highest"):
        w = _quantize(table.astype(F32).T, quant, (0,))
        return _act(_rmsnorm(x, final_norm, eps), quant) @ w


def logits(params, tokens, spec, rows=None, *, quant=None):
    x = hidden_states(params, tokens, spec, quant=quant)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(x, params["final_norm"], params["embed"],
                eps=float(spec["norm_eps"]), quant=quant)


def loss(params, tokens, spec, *, quant=None):
    """Mean next-token cross entropy of one sequence (S,)."""
    return nll(logits(params, tokens, spec, quant=quant)[:-1], tokens[1:])


def loss_and_grads(params, tokens, spec):
    """The loss of one sequence and its gradient in EVERY leaf (the tied
    table through the gather and through the head), float32: for the
    tests at small sizes."""
    as_f32 = jax.tree.map(lambda a: a.astype(F32), params)
    return jax.value_and_grad(lambda p: loss(p, tokens, spec))(as_f32)


def last_block_loss_and_grads(params, tokens, spec, *, quant=None):
    """Loss of one sequence and its gradients with respect to the LAST
    block's weights, the final norm and the head (``lm_head``: the tied
    table as the HEAD reads it, (hidden, V); the gather's part of the
    table's gradient runs through every layer and is not compared). The
    stream entering the last block is computed without gradient."""
    L = spec["num_hidden_layers"]
    eps = float(spec["norm_eps"])
    x_in = hidden_states(params, tokens, spec, quant=quant, upto=L - 1)
    tail = {"layer": jax.tree.map(lambda a: a.astype(F32),
                                  params["layers"][L - 1]),
            "final_norm": params["final_norm"].astype(F32),
            "lm_head": params["embed"].astype(F32).T}

    def f(tail):
        x, _ = block(x_in, tail["layer"], spec, L - 1, quant=quant)
        lg = head(x, tail["final_norm"], tail["lm_head"].T, eps=eps,
                  quant=quant)
        return nll(lg[:-1], tokens[1:])

    return jax.value_and_grad(f)(tail)
