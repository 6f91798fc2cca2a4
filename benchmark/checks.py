"""The comparisons that decide ``correct``: the program's own builders
(reached through the block's adapter) against the configuration's plain
reference, on weights and tokens from the seed.

Runs in the process that holds the chip, outside the measured window.
Every function returns ``{name: {"value": v, "limit": l}}``; a run is
correct when every value is at or under its limit. The limits are in
``benchmark/limits.json``, or in the file the configuration names, with
the two readings each was set from.
"""

from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import model_spec, weights

SERVE_PREFILL = 256     # tokens prefetched through the paged prefill
SERVE_DECODE = 8        # teacher-forced steps through the cache


def sample_tokens(spec: dict, seed: int, n: int, stream: int = 7):
    """(n,) seeded token ids; stream 7 keeps them apart from weights."""
    return jax.random.randint(weights.seed_key(seed, stream), (n,), 0,
                              spec["vocab_size"], dtype=jnp.int32)


def serve_reference_logits(params, spec: dict, tokens, *, quant=None):
    total = SERVE_PREFILL + SERVE_DECODE
    rows = list(range(SERVE_PREFILL - 1, total))
    return np.asarray(model_spec.reference(spec).logits(
        params, jnp.asarray(tokens)[:total], spec, rows, quant=quant))


def serve_check(params, spec: dict, seed: int, deployment: dict,
                engine=None) -> dict:
    """Logits of a SERVE_PREFILL-token prefill and SERVE_DECODE
    teacher-forced decode steps, by the adapter's
    ``serve_program_logits`` at the cell's deployment. ``engine``: the
    replica's idle engine, handed to an adapter whose
    ``serve_program_logits`` takes ``engine=`` and then compares the
    engine's own programs on its own cache; the others build a scratch
    copy beside it."""
    ref = model_spec.reference(spec)
    tokens = sample_tokens(spec, seed, SERVE_PREFILL + SERVE_DECODE)
    program_logits = model_spec.adapter(spec).serve_program_logits
    takes_engine = "engine" in inspect.signature(program_logits).parameters
    got = program_logits(params, spec, tokens, deployment,
                         prefill=SERVE_PREFILL,
                         **({"engine": engine} if takes_engine else {}))
    want = serve_reference_logits(params, spec, tokens)
    lim = model_spec.limits(spec)
    return {
        "serve_prefill_logits_rel_err": {
            "value": ref.rel_err(got[0], want[0]),
            "limit": lim["serve_prefill_logits_rel_err"]["limit"]},
        "serve_decode_logits_rel_err": {
            "value": ref.rel_err(got[1:], want[1:]),
            "limit": lim["serve_decode_logits_rel_err"]["limit"]},
    }


# ------------------------------------------------------------------ training
def _flat(tree):
    return jnp.concatenate([jnp.ravel(x).astype(jnp.float32)
                            for x in jax.tree.leaves(tree)])


def train_check(params, spec: dict, seed: int, seq: int, rules=None,
                quant=None) -> dict:
    """``quant`` set: the control takes the program's place."""
    ref = model_spec.reference(spec)
    tokens = sample_tokens(spec, seed, seq)
    want_loss, want_g = ref.last_block_loss_and_grads(params, tokens, spec)
    if quant is None:
        got_loss, got_g = model_spec.adapter(
            spec).train_program_loss_and_grads(params, spec, tokens, rules)
    else:
        got_loss, got_g = ref.last_block_loss_and_grads(
            params, tokens, spec, quant=quant)
    lim = model_spec.limits(spec)
    return {
        # printed, not judged: it does not tell the program from the control
        "train_loss_rel_err": {
            "value": abs(float(got_loss) - float(want_loss))
            / abs(float(want_loss)), "limit": None},
        "train_tail_grad_rel_err": {
            "value": ref.rel_err(_flat(got_g), _flat(want_g)),
            "limit": lim["train_tail_grad_rel_err"]["limit"]},
    }
