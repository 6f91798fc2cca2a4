"""Plain reference of the looped decoder (``model_type`` ``ouro``: the
family's report is "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741): the same ``L`` layers run ``T =
total_ut_steps`` times, the normed output of one pass the input of the
next, an exit gate after every pass, logits from the pass the gate's
cumulated probability selects.

With ``RMS(x; g)`` the RMSNorm at ``rms_norm_eps`` that scales by ``1 +
g`` (how the benchmark's weights store a norm) and ``q =
early_exit_threshold``::

    h_0 = E[tokens]
    for t in 0 .. T-1:
        x = h_t
        for l in 0 .. L-1:
            a = Attention_l(RMS(x; g1_l))     # 16 heads of 128, rotary in
            x = x + RMS(a; g2_l)              # the half-split layout at
            u = RMS(x; g3_l)                  # the token's position, causal
            m = W_down_l (silu(W_gate_l u) * (W_up_l u))
            x = x + RMS(m; g4_l)
        h_{t+1} = RMS(x; g_final)
        lambda_t = sigmoid(w_exit . h_{t+1} + b_exit)
    p_t = lambda_t prod_{s<t} (1 - lambda_s)  for t < T-1
    p_{T-1} = prod_{s<T-1} (1 - lambda_s)
    t* = the first t with p_0 + ... + p_t >= q, else T-1     (a position)
    logits = W_head h_{t*+1}

Straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``: no kernels, no cache (each
pass is a full causal forward of the whole sequence), no batching, no
scan. One sequence at a time, one layer's weights cast to float32 at a
time, so that it fits beside the system under test. It takes the
benchmark's own weights and tokens from the seed and imports nothing of
the program.

``quant`` turns the reference into the CONTROL, as in
``reference/dense_decoder.py`` (whose quantisers, norm, rotary and
``rel_err`` these are, imported): every weight matrix multiply of the
layers and the head with both operands rounded to int8 (``int8``), or
the weights alone (``int8w``, ``fp8``). The gate's dot product of 2,048
numbers stays in float32.

No train cell runs this block (``architectures/ouro.py`` ``NO_TRAIN``):
``last_block_loss_and_grads`` exits with the reason.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401
    F32, _act, _quantize, _rmsnorm, _rope, rel_err)     # rel_err: interface


@functools.partial(jax.jit, static_argnames=("theta", "eps", "quant"))
def block(x, layer, *, theta, eps, quant=None):
    """One layer of one pass on one sequence. x: (S, hidden) float32;
    layer: this layer's weights as stored (bfloat16). -> (x, k, v): the
    stream after the layer and the keys (rotated) and values it attended
    over, (S, KV, D) each."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in layer.items()}
        wq = _quantize(w["wq"], quant, (0,))
        wk = _quantize(w["wk"], quant, (0,))
        wv = _quantize(w["wv"], quant, (0,))
        wo = _quantize(w["wo"], quant, (0, 1))
        wg = _quantize(w["w_gate"], quant, (0,))
        wu = _quantize(w["w_up"], quant, (0,))
        wd = _quantize(w["w_down"], quant, (0,))
        S = x.shape[0]
        H, KV, D = wq.shape[1], wk.shape[1], wq.shape[2]
        h = _act(_rmsnorm(x, w["attn_norm"], eps), quant)
        q = _rope(jnp.einsum("se,ehd->shd", h, wq), theta)
        k = _rope(jnp.einsum("se,ehd->shd", h, wk), theta)
        v = jnp.einsum("se,ehd->shd", h, wv)
        kr = jnp.repeat(k, H // KV, axis=1)
        vr = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, kr) * (D ** -0.5)
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, vr)
        a = jnp.einsum("shd,hde->se",
                       _act(a.reshape(S, -1), quant).reshape(a.shape), wo)
        x = x + _rmsnorm(a, w["attn_post_norm"], eps)
        u = _act(_rmsnorm(x, w["mlp_norm"], eps), quant)
        g = jnp.einsum("se,em->sm", u, wg)
        up = jnp.einsum("se,em->sm", u, wu)
        m = jnp.einsum("sm,me->se", _act(jax.nn.silu(g) * up, quant), wd)
        return x + _rmsnorm(m, w["mlp_post_norm"], eps), k, v


@functools.partial(jax.jit, static_argnames=("eps",))
def between_passes(x, final_norm, exit_w, exit_b, *, eps):
    """-> (h_{t+1} (S, hidden), lambda_t (S,))."""
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, final_norm, eps)
        return h, jax.nn.sigmoid(h @ exit_w.astype(F32)
                                 + exit_b.astype(F32)[0])


@functools.partial(jax.jit, static_argnames=("quant",))
def head(h, lm_head, *, quant=None):
    """The output head on chosen rows of a normed state. -> (rows,
    vocab) float32."""
    with jax.default_matmul_precision("highest"):
        return _act(h, quant) @ _quantize(lm_head.astype(F32), quant, (0,))


def exit_distribution(lams):
    """lambdas (T, S) -> p (T, S)."""
    T = lams.shape[0]
    rows, stay = [], jnp.ones_like(lams[0])
    for t in range(T - 1):
        rows.append(lams[t] * stay)
        stay = stay * (1.0 - lams[t])
    return jnp.stack(rows + [stay])


def exit_pass(p, threshold):
    """(S,) int: the first pass whose cumulated probability reaches
    ``threshold``, else the last."""
    T = p.shape[0]
    chosen = jnp.full(p.shape[1:], T - 1, jnp.int32)
    for t in range(T - 1, -1, -1):
        chosen = jnp.where(jnp.sum(p[:t + 1], axis=0) >= threshold, t,
                           chosen)
    return chosen


def passes(params, tokens, spec, *, quant=None, keep_kv=False):
    """Every pass of one sequence of token ids (S,). -> (states (T, S,
    hidden): ``h_1 .. h_T``, lambdas (T, S), kv: where ``keep_kv`` a
    list of ``T * L`` (k, v) pairs in cache order ``t * L + l``)."""
    L, T = spec["num_hidden_layers"], spec["total_ut_steps"]
    theta, eps = float(spec["rope_theta"]), float(spec["rms_norm_eps"])
    h = params["embed"][tokens].astype(F32)
    states, lams, kv = [], [], []
    for _ in range(T):
        x = h
        for l in range(L):
            layer = jax.tree.map(lambda a: a[l], params["layers"])
            x, k, v = block(x, layer, theta=theta, eps=eps, quant=quant)
            if keep_kv:
                kv.append((k, v))
        h, lam = between_passes(x, params["final_norm"], params["exit_w"],
                                params["exit_b"], eps=eps)
        states.append(h)
        lams.append(lam)
    return jnp.stack(states), jnp.stack(lams), kv


def logits(params, tokens, spec, rows=None, *, quant=None, detail=False):
    """Logits (rows, vocab) float32 of one sequence; ``rows`` picks
    positions (all by default). ``detail``: (logits, the chosen pass of
    each row, p (T, rows))."""
    states, lams, _ = passes(params, tokens, spec, quant=quant)
    if rows is not None:
        rows = jnp.asarray(rows)
        states, lams = states[:, rows], lams[:, rows]
    p = exit_distribution(lams)
    chosen = exit_pass(p, float(spec["early_exit_threshold"]))
    h = jnp.take_along_axis(states, chosen[None, :, None], axis=0)[0]
    out = head(h, params["lm_head"], quant=quant)
    return (out, chosen, p) if detail else out


def last_block_loss_and_grads(params, tokens, spec, *, quant=None):
    raise SystemExit("the ouro reference has no backward pass: no train "
                     "cell runs this block (the family's loss is expected "
                     "over the exit distribution, with an entropy term)")
