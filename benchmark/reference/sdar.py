"""Plain reference of the ``sdar_moe`` language model: the Qwen3-MoE
layer under a BLOCK-CAUSAL mask, and generation by diffusion over blocks.

Straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, no sorting of tokens. One sequence at a time, layer by layer,
one layer's attention weights and ONE expert's matrices cast to float32
at a time, the head in runs of columns, so that it fits beside a replica
that holds 10 GB of weights. The interface of ``dense_decoder.py``,
whose rounding helpers (the int8 control) and ``rel_err`` it shares; it
imports nothing of the program.

The layer, for an input ``x`` (T, hidden) (``configs/sdar-30b-a3b-l7.json``
repeats the keys and lists what is ``assumed``):

- ``h = rmsnorm(x, w_in)``; ``q = h Wq`` as (T, heads, head_dim), ``k = h
  Wk``, ``v = h Wv`` as (T, kv_heads, head_dim); ``q = rmsnorm(q, w_qn)``
  and ``k = rmsnorm(k, w_kn)`` over the head's width (one vector each,
  shared by the heads); rotary on the whole head (half-split pairs) at
  ``rope_theta``, position = index in the sequence; scores ``q k^T /
  sqrt(head_dim)``, query head i against KV head ``i // (heads /
  kv_heads)``; position t sees position s iff ``s // B <= t // B`` with
  ``B = block_length``: causal between blocks, everything inside its own
  block; ``x = x + (softmax(scores) v) Wo``.
- ``g = rmsnorm(x, w_post)``; ``p = softmax(g Wr)`` over ``num_experts``
  in float32, the ``num_experts_per_tok`` largest kept and divided by
  their sum (``norm_topk_prob``); ``x = x + sum_e p_e (silu(g Wgate_e) *
  (g Wup_e)) Wdown_e``. No shared expert, no dense layer.
- After the last layer ``rmsnorm``, then the untied head. Every norm's
  gain is ``1 + w``, as the harness stores every norm; eps
  ``rms_norm_eps``.

The logit row at position t scores the token AT position t (no shift).
:func:`logits` is ONE forward over whatever ids it is given: the mask id
is an id like any other. :func:`decide` is the static rule on plain
arrays and :func:`generate` the family's loop with no cache, for the
tests on the CPU.

``quant`` makes the CONTROL (see ``dense_decoder.py``): every weight
matrix multiply of attention, the experts and the head in int8 / fp8.
The router stays in float32 in the control too, as in the other routed
references: rounding it would fail the control for a reason of its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dense_decoder import (F32, _act, _quantize,
                                               _rmsnorm, _rope, head, nll,
                                               rel_err)
from benchmark.reference.laguna import route        # softmax, top-k, f32
from benchmark.reference.mimo_v2 import swiglu      # one SwiGLU, float32

__all__ = ["logits", "last_block_loss_and_grads", "rel_err", "nll",
           "decide", "generate", "hidden_states", "routed_mlp"]

ATTENTION_KEYS = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo")
HEAD_COLUMNS = 32768        # of the vocabulary, in float32 at a time
NO_TRAIN = ("the sdar reference has no backward pass: no train cell runs "
            "this block (the family trains under a diffusion loss over "
            "noised blocks, which the train path has not, and at 16 bytes "
            "a parameter it fits only as one of 8 chips that share each "
            "layer)")


@functools.partial(jax.jit, static_argnames=("theta", "eps", "span",
                                             "quant"))
def attention(x, layer, *, theta, eps, span, quant=None):
    """x + attn(norm(x)) on one sequence (S, hidden) float32 under the
    block-causal mask of blocks of ``span`` positions."""
    with jax.default_matmul_precision("highest"):
        w = {k: layer[k].astype(F32) for k in ATTENTION_KEYS}
        wq = _quantize(w["wq"], quant, (0,))
        wk = _quantize(w["wk"], quant, (0,))
        wv = _quantize(w["wv"], quant, (0,))
        wo = _quantize(w["wo"], quant, (0, 1))
        S = x.shape[0]
        H, KV, D = wq.shape[1], wk.shape[1], wq.shape[2]
        h = _act(_rmsnorm(x, w["attn_norm"], eps), quant)
        q = _rmsnorm(jnp.einsum("se,ehd->shd", h, wq), w["q_norm"], eps)
        k = _rmsnorm(jnp.einsum("se,ehd->shd", h, wk), w["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        v = jnp.einsum("se,ehd->shd", h, wv)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * (D ** -0.5)
        t, u = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        seen = u // span <= t // span
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v)
        return x + jnp.einsum(
            "shd,hde->se", _act(a.reshape(S, -1), quant).reshape(a.shape),
            wo)


def routed_mlp(h, layer, spec, *, quant=None):
    """The routed MLP's sum over the experts held (``experts_first`` on,
    ``num_experts`` of them: all where the configuration holds every
    expert) for normed activations h (S, hidden) float32. -> (sum, share
    of choices that bf16 activations would flip)."""
    first = spec.get("experts_first", 0)
    w, flipped = route(h, layer["router"],
                       top_k=spec["num_experts_per_tok"], scale=1.0)
    out = jnp.zeros_like(h)
    for e in range(spec["num_experts"]):
        y = swiglu(h, layer["we_gate"][e], layer["we_up"][e],
                   layer["we_down"][e], quant=quant)
        out = out + w[:, first + e, None] * y
    return out, flipped


def block(x, layer, spec, *, quant=None):
    """One layer on one sequence. -> (x, flipped share)."""
    eps = float(spec["rms_norm_eps"])
    x = attention(x, {k: layer[k] for k in ATTENTION_KEYS},
                  theta=float(spec["rope_theta"]), eps=eps,
                  span=int(spec["block_length"]), quant=quant)
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, layer["mlp_norm"].astype(F32), eps)
    y, flipped = routed_mlp(h, layer, spec, quant=quant)
    return x + y, flipped


def hidden_states(params, tokens, spec, *, quant=None, upto=None,
                  quiet=False):
    n = spec["num_hidden_layers"] if upto is None else upto
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    flips = []
    for l in range(n):
        x, flipped = block(x, params["layers"][l], spec, quant=quant)
        flips.append(flipped)
    if quant is None and flips and not quiet:
        # read, not judged: what bfloat16 activations do to the choices
        print("read router_choices_flipped_by_bf16_activations: share by "
              f"layer {[round(float(f), 5) for f in flips]}", flush=True)
    return x


def logits(params, tokens, spec, rows=None, *, quant=None, quiet=False):
    """Logits (rows, vocab) float32 of ONE forward of one sequence under
    the block-causal mask; ``rows`` picks positions (all by default)."""
    x = hidden_states(params, tokens, spec, quant=quant, quiet=quiet)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    V = params["lm_head"].shape[1]
    return jnp.concatenate([
        head(x, params["final_norm"], params["lm_head"][:, a:a + HEAD_COLUMNS],
             eps=float(spec["rms_norm_eps"]), quant=quant)
        for a in range(0, V, HEAD_COLUMNS)], axis=-1)


def last_block_loss_and_grads(params, tokens, spec, *, quant=None):
    raise SystemExit(NO_TRAIN)


# -------------------------------------------------------------- generation
def decide(block_logits, decided, k: int):
    """``low_confidence_static`` on plain arrays: block_logits (B, vocab)
    float32, decided (B,) bool. The token picked at each position is the
    argmax, its confidence that token's softmax probability; of the
    undecided positions the ``k`` most confident are decided now, equal
    confidences the lower position first. -> (picked (B,), now (B,)
    bool)."""
    z = np.asarray(block_logits, np.float32)
    decided = np.asarray(decided, bool)
    picked = z.argmax(axis=-1)
    top = z.max(axis=-1)
    conf = (1.0 / np.exp(z - top[:, None]).sum(axis=-1, dtype=np.float32)
            ).astype(np.float32)
    order = sorted((i for i in range(len(decided)) if not decided[i]),
                   key=lambda i: (-conf[i], i))
    now = np.zeros(len(decided), bool)
    now[order[:k]] = True
    return picked, now


def generate(params, prompt, max_tokens: int, spec, denoising_steps=None):
    """The family's greedy loop with NO cache: every step is one whole
    forward of the committed tokens and the block. The prompt's whole
    blocks stand; its tail is the decided part of the first block; every
    other position starts undecided and is fed ``mask_token_id``. A block
    decides ``ceil(undecided at its start / denoising_steps)`` positions
    a step until none is left, is committed as it stands, and the next
    begins; the answer is cut at ``max_tokens``."""
    B, mask = int(spec["block_length"]), int(spec["mask_token_id"])
    steps = int(denoising_steps or spec["denoising_steps"])
    prompt = [int(t) for t in prompt]
    whole = len(prompt) - len(prompt) % B
    seq, out = prompt[:whole], []
    ids = prompt[whole:] + [0] * (B - len(prompt) + whole)
    decided = np.arange(B) < len(prompt) - whole
    while len(out) < max_tokens:
        first = int(decided.sum())
        quota = -(-(B - first) // steps)
        while not decided.all():
            fed = [t if d else mask for t, d in zip(ids, decided)]
            lg = logits(params, np.asarray(seq + fed, np.int32), spec,
                        rows=list(range(len(seq), len(seq) + B)),
                        quiet=True)
            picked, now = decide(lg, decided,
                                 min(quota, int((~decided).sum())))
            ids = [int(p) if n else t for t, p, n in zip(ids, picked, now)]
            decided = decided | now
        seq, out = seq + ids, out + ids[first:]
        ids, decided = [0] * B, np.zeros(B, bool)
    return out[:max_tokens]
