"""The comparisons that decide ``correct``: the program's own builders
against ``benchmark/reference`` on weights and tokens from the seed.

Runs in the process that holds the chip, outside the measured window.
Every function returns ``{name: {"value": v, "limit": l}}``; a run is
correct when every value is at or under its limit. The limits are in
``benchmark/limits.json`` with the two readings each was set from.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import model_spec, weights
from benchmark.reference import dense_decoder as ref

SERVE_PREFILL = 256     # tokens prefetched through the paged prefill
SERVE_DECODE = 8        # teacher-forced steps through the cache


def limits() -> dict:
    with open(os.path.join(model_spec.HERE, "limits.json")) as f:
        return json.load(f)["limits"]


def program_config(spec: dict):
    from ray_tpu.models import llama

    return llama.LlamaConfig(**model_spec.program_kwargs(spec))


def sample_tokens(spec: dict, seed: int, n: int, stream: int = 7):
    """(n,) seeded token ids; stream 7 keeps them apart from weights."""
    return jax.random.randint(weights.seed_key(seed, stream), (n,), 0,
                              spec["vocab_size"], dtype=jnp.int32)


def serve_program_logits(params, spec: dict, tokens, *, num_slots: int,
                         max_seq: int, block_size: int):
    """Prefill of the first SERVE_PREFILL tokens, then SERVE_DECODE
    teacher-forced decode steps through a scratch pool, with the
    builders the engine uses. -> (1 + SERVE_DECODE, vocab) float32: the
    logits at positions SERVE_PREFILL - 1 .. SERVE_PREFILL +
    SERVE_DECODE - 1."""
    from ray_tpu.models.paged_cache import (
        BlockAllocator, PagedConfig, init_paged_cache,
        make_paged_decode_step, make_paged_prefill, pad_to_block_bucket)

    cfg = program_config(spec)
    total = SERVE_PREFILL + SERVE_DECODE
    page = PagedConfig(num_blocks=2 + -(-(total + 1) // block_size),
                       block_size=block_size, max_seq=max_seq)
    alloc = BlockAllocator(page, num_slots)
    cache = init_paged_cache(cfg, page, num_slots)
    prefill = make_paged_prefill(params, cfg, page)
    decode = make_paged_decode_step(params, cfg, page)
    slot = num_slots - 1                  # not the first: indexing shows
    if not alloc.ensure(slot, total + 1):
        raise RuntimeError("the scratch pool is too small for the check")
    P = pad_to_block_bucket(SERVE_PREFILL, block_size)
    padded = np.zeros((1, P), np.int32)
    toks = np.asarray(tokens)
    padded[0, :SERVE_PREFILL] = toks[:SERVE_PREFILL]
    cache, lg = prefill(cache, alloc.tables[slot], jnp.asarray(padded),
                        SERVE_PREFILL, slot)
    rows = [np.asarray(lg, np.float32).reshape(-1)]
    active = np.zeros(num_slots, bool)
    active[slot] = True
    for i in range(SERVE_DECODE):
        last = np.zeros(num_slots, np.int32)
        last[slot] = toks[SERVE_PREFILL + i]
        cache, lg = decode(cache, alloc.device_tables(), jnp.asarray(last),
                           jnp.asarray(active))
        rows.append(np.asarray(lg, np.float32)[slot])
    return np.stack(rows)


def serve_reference_logits(params, spec: dict, tokens, *, quant=None):
    total = SERVE_PREFILL + SERVE_DECODE
    rows = list(range(SERVE_PREFILL - 1, total))
    return np.asarray(ref.logits(params, jnp.asarray(tokens)[:total], spec,
                                 rows, quant=quant))


def serve_check(params, spec: dict, seed: int, *, num_slots: int,
                max_seq: int, block_size: int) -> dict:
    tokens = sample_tokens(spec, seed, SERVE_PREFILL + SERVE_DECODE)
    got = serve_program_logits(params, spec, tokens, num_slots=num_slots,
                               max_seq=max_seq, block_size=block_size)
    want = serve_reference_logits(params, spec, tokens)
    lim = limits()
    return {
        "serve_prefill_logits_rel_err": {
            "value": ref.rel_err(got[0], want[0]),
            "limit": lim["serve_prefill_logits_rel_err"]["limit"]},
        "serve_decode_logits_rel_err": {
            "value": ref.rel_err(got[1:], want[1:]),
            "limit": lim["serve_decode_logits_rel_err"]["limit"]},
    }


# ------------------------------------------------------------------ training
def train_program_loss_and_grads(params, spec: dict, tokens, rules=None):
    """The program's loss and gradients on one sequence, through the
    code the train step differentiates (``llama.loss_fn``: flash forward
    and backward kernels, remat scan). Returns the loss and the
    gradients of the last block, the final norm and the head."""
    from ray_tpu.models import llama

    cfg = program_config(spec)
    L = spec["num_hidden_layers"]

    def f(p, toks):
        return llama.loss_fn(p, {"tokens": toks[None, :]}, cfg, rules)[0]

    def tail_of(p, toks):
        loss, g = jax.value_and_grad(f)(p, toks)
        return loss, {"layer": jax.tree.map(lambda a: a[L - 1], g["layers"]),
                      "final_norm": g["final_norm"],
                      "lm_head": g["lm_head"]}

    return jax.jit(tail_of)(params, tokens)


def _flat(tree):
    return jnp.concatenate([jnp.ravel(x).astype(jnp.float32)
                            for x in jax.tree.leaves(tree)])


def train_check(params, spec: dict, seed: int, seq: int, rules=None,
                quant=None) -> dict:
    """``quant`` set: the control takes the program's place."""
    tokens = sample_tokens(spec, seed, seq)
    want_loss, want_g = ref.last_block_loss_and_grads(params, tokens, spec)
    if quant is None:
        got_loss, got_g = train_program_loss_and_grads(params, spec, tokens,
                                                       rules)
    else:
        got_loss, got_g = ref.last_block_loss_and_grads(
            params, tokens, spec, quant=quant)
    lim = limits()
    return {
        # printed, not judged: it does not tell the program from the control
        "train_loss_rel_err": {
            "value": abs(float(got_loss) - float(want_loss))
            / abs(float(want_loss)), "limit": None},
        "train_tail_grad_rel_err": {
            "value": ref.rel_err(_flat(got_g), _flat(want_g)),
            "limit": lim["train_tail_grad_rel_err"]["limit"]},
    }
