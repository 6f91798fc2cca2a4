"""Plain reference of the dense decoder block that both configurations
share: RMSNorm (gain ``1 + w``), rotary embeddings in the half-split
("neox") layout, grouped-query causal attention, SwiGLU, no biases,
untied head.

Straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, no scan. One sequence at a time, one layer's weights cast to
float32 at a time, so that it fits beside the system under test. It
takes the benchmark's own weights (``benchmark/weights.py``) and tokens
from the seed and nothing the program has made.

Departures from the published models: DeepSeek-Coder's linear rope
scaling is not applied (``benchmark/configs/deepseek-coder-1.3b.json``).

``quant`` turns the reference into the CONTROL: the same arithmetic with
every weight matrix multiply computed in the next precision below
bfloat16. ``int8`` is what the v5e's integer units take: both operands
rounded to int8, weights with one scale for each output channel,
activations with one for each token. ``int8w`` and ``fp8`` round the
weights alone. A comparison that passes the control proves nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _quantize(w, mode, contract_axes):
    """Round ``w`` (float32) to int8 or fp8-e4m3 and back, one scale for
    each output channel (the axes that are not contracted)."""
    if mode is None:
        return w
    mode = "int8" if mode == "int8w" else mode
    amax = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) + 1e-30
    if mode == "int8":
        scale = amax / 127.0
        rounded = jnp.round(w / scale).clip(-127, 127) * scale
    elif mode == "fp8":
        scale = amax / 448.0
        rounded = (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    else:
        raise ValueError(f"quant={mode!r}")
    # straight through: gradients see the rounded weight's value
    return w + jax.lax.stop_gradient(rounded - w)


def _act(x, mode):
    """Activations entering a weight matmul: rounded to int8 with one
    scale for each token when the control computes in int8."""
    return _quantize(x, "int8", (-1,)) if mode == "int8" else x


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(F32))


def _rope(x, theta):
    """x: (S, heads, D). Position p rotates the pair (x[i], x[i + D/2])
    by p * theta ** (-2 i / D)."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]     # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "quant"))
def block(x, layer, *, theta, eps, quant=None):
    """One decoder block on one sequence. x: (S, hidden) float32; layer:
    this block's weights as stored (bfloat16)."""
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
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * (D ** -0.5)
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v)
        x = x + jnp.einsum("shd,hde->se",
                           _act(a.reshape(S, -1), quant).reshape(a.shape), wo)
        h = _act(_rmsnorm(x, w["mlp_norm"], eps), quant)
        g = jnp.einsum("se,em->sm", h, wg)
        u = jnp.einsum("se,em->sm", h, wu)
        return x + jnp.einsum("sm,me->se",
                              _act(jax.nn.silu(g) * u, quant), wd)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, final_norm, lm_head, *, eps, quant=None):
    """Final norm and output head on chosen rows. -> (rows, vocab) f32."""
    with jax.default_matmul_precision("highest"):
        w = _quantize(lm_head.astype(F32), quant, (0,))
        return _act(_rmsnorm(x, final_norm, eps), quant) @ w


def hidden_states(params, tokens, spec, *, quant=None, upto=None):
    """Residual stream (S, hidden) float32 after ``upto`` blocks (all by
    default) for one sequence of token ids (S,)."""
    n = spec["num_hidden_layers"] if upto is None else upto
    x = params["embed"][tokens].astype(F32)
    for i in range(n):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x = block(x, layer, theta=float(spec["rope_theta"]),
                  eps=float(spec["rms_norm_eps"]), quant=quant)
    return x


def logits(params, tokens, spec, rows=None, *, quant=None):
    """Logits (rows, vocab) float32 of one sequence; ``rows`` picks
    positions (all by default)."""
    x = hidden_states(params, tokens, spec, quant=quant)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    lm_head = (params["embed"].T if spec["tie_word_embeddings"]
               else params["lm_head"])
    return head(x, params["final_norm"], lm_head,
                eps=float(spec["rms_norm_eps"]), quant=quant)


def nll(logits_, targets):
    """Mean next-token cross entropy: logits (S-1, V) against (S-1,)."""
    logp = jax.nn.log_softmax(logits_, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], -1))


def loss(params, tokens, spec, *, quant=None):
    """Next-token loss of one sequence (S,), as the program's loss_fn
    defines it: position i predicts token i + 1, mean over S - 1."""
    return nll(logits(params, tokens, spec, quant=quant)[:-1], tokens[1:])


def last_block_loss_and_grads(params, tokens, spec, *, quant=None):
    """Loss of one sequence and its gradients with respect to the LAST
    block's weights, the final norm and the head: as much of the backward
    pass as float32 holds beside the system. The stream entering the last
    block is computed without gradient."""
    L = spec["num_hidden_layers"]
    theta, eps = float(spec["rope_theta"]), float(spec["rms_norm_eps"])
    x_in = hidden_states(params, tokens, spec, quant=quant, upto=L - 1)
    last = jax.tree.map(lambda a: a[L - 1].astype(F32), params["layers"])
    tail = {"layer": last, "final_norm": params["final_norm"].astype(F32),
            "lm_head": params["lm_head"].astype(F32)}

    def f(tail):
        x = block(x_in, tail["layer"], theta=theta, eps=eps, quant=quant)
        lg = head(x, tail["final_norm"], tail["lm_head"], eps=eps,
                  quant=quant)
        return nll(lg[:-1], tokens[1:])

    return jax.value_and_grad(f)(tail)


def rel_err(a, b):
    """||a - b|| / ||b|| in float32: the number every comparison uses."""
    a, b = jnp.asarray(a, F32), jnp.asarray(b, F32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / (jnp.linalg.norm(b.ravel()) + 1e-30))
