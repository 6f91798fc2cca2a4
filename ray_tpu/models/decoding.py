"""KV-cache inference: slot-based prefill/decode for continuous batching.

Net-new vs the reference (which delegates LLM inference to vLLM —
``python/ray/llm/_internal/serve/deployments/llm/vllm/``). TPU-first
design choices:

- **Fixed shapes**: the cache is a (layers, slots, max_seq, kv_heads, hd)
  ring of slots; prefill and decode are jitted once per (bucketed) shape —
  no dynamic shapes, no recompiles in steady state.
- **Slot model**: each active request owns one batch row ("slot") with its
  own length counter; the decode step advances ALL active slots one token
  (Orca-style continuous batching; the engine in
  ``ray_tpu.serve.llm`` admits/evicts slots between steps).
- **Functional cache**: jitted steps take and return the cache arrays
  (donated), so XLA updates them in place on device.

**Where the block lives.** The dense decoder's arithmetic is
:mod:`ray_tpu.models.llama`'s (``qkv``, ``mlp``, ``embed``, ``logits_f32``).
:func:`dense_block` strings it into one serving block around an ``attend``
function; :func:`ray_tpu.ops.attention.attend_rows` is the one attention
over cached rows. A
builder, here and in :mod:`ray_tpu.models.paged_cache`, adds its index
arithmetic and its ``attend``: where this call's K and V rows are written,
and what the queries attend over.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import (LlamaConfig, Params, embed, logits_f32,
                                  mlp, qkv)
from ray_tpu.ops.attention import attend_rows, mha_reference
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.pallas.decode_attention import slot_decode
from ray_tpu.ops.rope import rope_frequencies
from ray_tpu.util.profiling import part

Cache = Dict[str, jax.Array]


def init_cache(config: LlamaConfig, num_slots: int,
               max_seq: Optional[int] = None, dtype=None) -> Cache:
    c = config
    S = max_seq or c.max_seq
    dt = dtype or c.dtype
    shape = (c.n_layers, num_slots, S, c.n_kv_heads, c.head_dim)
    return {
        "k": jnp.zeros(shape, dt),
        "v": jnp.zeros(shape, dt),
        "length": jnp.zeros((num_slots,), jnp.int32),
    }


def dense_block(x, layer, c: LlamaConfig, cos, sin, positions, attend,
                state):
    """One block of the dense decoder in a serving program: norm, q / k /
    v at ``positions``, ``out, state = attend(q, k, v, state)``, output
    projection, norm, MLP. x (B, S, E) -> (x, state).

    ``attend`` is what makes a program: it writes this call's k and v rows
    into its cache and attends over what the queries may see; ``state`` is
    what it threads through the layers (a layer's cache rows, or the whole
    pool and the layer's index)."""
    with part("attn_proj"):
        q, k, v = qkv(rmsnorm(x, layer["attn_norm"], c.norm_eps), layer,
                      cos, sin, positions)
    out, state = attend(q, k, v, state)
    with part("attn_proj"):
        x = x + jnp.einsum("bshd,hde->bse", out,
                           layer["wo"].astype(x.dtype))
    with part("mlp"):
        x = x + mlp(rmsnorm(x, layer["mlp_norm"], c.norm_eps), layer)
    return x, state


def _bind_params(jitted, params: Params):
    """The builders' outer signatures take no weights: bind the engine's
    one device-resident ``params`` as the program's first argument at
    call time. A closed-over array would lower to a literal — a copy of
    the weights in every compiled program. ``call.jitted`` is the
    program itself (``params`` first), for lowering from shapes."""

    def call(*args):
        return jitted(params, *args)

    call.jitted = jitted
    return call


def _bind_padded(jitted, params: Params, tokens_at: int, multiple_of: int = 1):
    """:func:`_bind_params` for a program jitted per padded length:
    ``call(cache, *args)`` where ``args[tokens_at]`` is the (B, P) token
    array, whose P is the program's static ``pad_len`` (refused unless a
    multiple of ``multiple_of``, the paged programs' block size), and
    every other argument a scalar or vector made int32."""

    def call(cache, *args):
        pad_len = args[tokens_at].shape[1]
        if pad_len % multiple_of:
            raise ValueError(f"padded length {pad_len} not a multiple of "
                             f"block_size {multiple_of}")
        args = [a if i == tokens_at else jnp.asarray(a, jnp.int32)
                for i, a in enumerate(args)]
        return jitted(params, cache, *args, pad_len=pad_len)

    call.jitted = jitted
    return call


def _scan_cache(attend, x, params: Params, cache: Cache, c: LlamaConfig,
                positions):
    """:func:`dense_block` over the layers, each layer's cache rows
    (slots, S, KV, D) through the scan's ``xs`` / ``ys`` as ``attend``'s
    ``state = (k_rows, v_rows)``. -> (x, (new k, new v))."""
    cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)

    def body(x, scanned):
        layer, kc, vc = scanned
        return dense_block(x, layer, c, cos, sin, positions, attend,
                           (kc, vc))

    return jax.lax.scan(body, x,
                        (params["layers"], cache["k"], cache["v"]))


@part("kv_store")
def _put_rows(rows_all, new, valid, slot, start):
    """Write ``new`` (1, P, KV, D), zeroed where not ``valid`` (P,), into
    rows [start, start + P) of ``slot`` in a layer's (slots, S, KV, D)."""
    return jax.lax.dynamic_update_slice(
        rows_all, jnp.where(valid[None, :, None, None], new,
                            0.0).astype(rows_all.dtype),
        (slot, start, 0, 0))


def make_decode_step(params: Params, config: LlamaConfig):
    """Build the jitted one-token-for-all-slots decode step.

    step(cache, tokens (B,) int32, active (B,) bool) →
        (cache, logits (B, vocab) f32)
    Inactive slots pass through untouched (their length doesn't advance).
    """
    c = config

    def step(params: Params, cache: Cache, tokens: jax.Array,
             active: jax.Array):
        lengths = cache["length"]              # count BEFORE this token
        slot_ids = jnp.arange(tokens.shape[0])

        def attend(q, k, v, state):
            # write new k/v at each slot's current length
            kc, vc = state                                 # (B, S, KV, D)
            with part("kv_store"):
                kc = kc.at[slot_ids, lengths].set(k[:, 0])
                vc = vc.at[slot_ids, lengths].set(v[:, 0])
            return (slot_decode(q, kc, vc, lengths + 1,
                                scale=c.head_dim ** -0.5), (kc, vc))

        x = embed(params, tokens, c)[:, None, :]                 # (B,1,E)
        x, (new_k, new_v) = _scan_cache(attend, x, params, cache, c,
                                        lengths[:, None])
        logits = logits_f32(x, params, c, row=(slice(None), 0))
        # only active slots advance / keep their writes
        keep = active[:, None, None, None]
        new_k = jnp.where(keep[None], new_k, cache["k"])
        new_v = jnp.where(keep[None], new_v, cache["v"])
        new_len = jnp.where(active, lengths + 1, lengths)
        return ({"k": new_k, "v": new_v, "length": new_len}, logits)

    return _bind_params(jax.jit(step, donate_argnums=(1,)), params)


def make_prefill(params: Params, config: LlamaConfig):
    """Build the jitted single-slot prefill.

    prefill(cache, tokens (1, P) padded, true_len, slot) →
        (cache, last_logits (vocab,) f32)
    Jitted per padded length P (bucket prompt lengths to limit compiles).
    """
    c = config

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("pad_len",))
    def prefill(params: Params, cache: Cache, tokens: jax.Array,
                true_len: jax.Array, slot: jax.Array, pad_len: int):
        positions = jnp.arange(pad_len)[None, :]
        mask_valid = positions[0] < true_len                 # (P,)

        def attend(q, k, v, state):
            # causal within the prompt; its k/v go to the slot's rows [0, P)
            kc_all, vc_all = state                           # (slots, S, …)
            return mha_reference(q, k, v, causal=True), (
                _put_rows(kc_all, k, mask_valid, slot, 0),
                _put_rows(vc_all, v, mask_valid, slot, 0))

        x = embed(params, tokens, c)                         # (1, P, E)
        x, (new_k, new_v) = _scan_cache(attend, x, params, cache, c,
                                        positions)
        logits = logits_f32(x, params, c,
                            row=(0, jnp.maximum(true_len - 1, 0)))
        new_len = cache["length"].at[slot].set(true_len)
        return ({"k": new_k, "v": new_v, "length": new_len}, logits)

    return _bind_padded(prefill, params, tokens_at=0)


def make_chunked_prefill(params: Params, config: LlamaConfig):
    """Build the jitted chunked prefill (vLLM-class chunked prefill /
    Sarathi-style): process one fixed-size chunk of a long prompt per
    call, attending causally within the chunk AND over the slot's
    already-written prefix rows — so the engine can interleave decode
    steps of other slots between chunks instead of stalling them for a
    whole long-prompt prefill.

    chunk(cache, tokens (1, C) padded, true_len-in-chunk, start_pos,
          slot) → (cache, last_logits (vocab,) f32)

    One compile per chunk size C. ``cache["length"]`` for the slot
    becomes ``start_pos + true_len`` after the call (callers pass the
    running offset); the returned logits are for the chunk's last valid
    token (only meaningful on the final chunk).
    """
    c = config

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("pad_len",))
    def chunk(params: Params, cache: Cache, tokens: jax.Array,
              true_len: jax.Array, start_pos: jax.Array, slot: jax.Array,
              pad_len: int):
        rel = jnp.arange(pad_len)                            # (C,)
        positions = (start_pos + rel)[None, :]               # (1, C)
        mask_valid = rel < true_len                          # (C,)

        def attend(q, k, v, state):
            # write the chunk's k/v at rows [start_pos, start_pos + C),
            # then attend over the slot's FULL row set (prefix + chunk)
            kc_all, vc_all = state                           # (slots, S, …)
            kc_all = _put_rows(kc_all, k, mask_valid, slot, start_pos)
            vc_all = _put_rows(vc_all, v, mask_valid, slot, start_pos)
            out = attend_rows(q, kc_all[slot][None], vc_all[slot][None],
                              positions, c.head_dim ** -0.5)
            return out, (kc_all, vc_all)

        x = embed(params, tokens, c)                         # (1, C, E)
        x, (new_k, new_v) = _scan_cache(attend, x, params, cache, c,
                                        positions)
        logits = logits_f32(x, params, c,
                            row=(0, jnp.maximum(true_len - 1, 0)))
        new_len = cache["length"].at[slot].set(start_pos + true_len)
        return ({"k": new_k, "v": new_v, "length": new_len}, logits)

    return _bind_padded(chunk, params, tokens_at=0)


def make_batched_spec_verify(params: Params, config: LlamaConfig,
                             with_logits: bool = True):
    """Speculative-decoding verify: score K+1 candidate tokens for EVERY
    slot in ONE forward (the speculation subsystem's target-model step —
    :mod:`ray_tpu.models.speculation` owns proposers and acceptance).

    verify(cache, tokens (B, C), true_lens (B,), start_pos (B,)) →
        (cache, all_logits (B, C, vocab) f32)

    B must equal the cache's slot count. Per slot, ``tokens[b, :true_lens
    [b]]`` is the window [pending_token, proposals...] written at rows
    [start_pos[b], start_pos[b] + true_lens[b]); ``true_lens[b] == 1`` is
    a plain decode step for that slot and ``true_lens[b] == 0`` leaves it
    untouched (inactive) — one compiled program serves speculating,
    non-speculating, and idle slots alike under continuous batching.

    Cache rows for every valid window position are written; rejected
    rows sit beyond the accepted length the caller installs afterwards
    (the engine overwrites ``cache["length"]`` wholesale) and are
    overwritten by later writes — attention masks by position, so they
    are invisible.

    ``with_logits=False`` is :func:`make_kv_ingest`'s program: the same
    rows written, no head."""
    c = config

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("pad_len",))
    def verify(params: Params, cache: Cache, tokens: jax.Array,
               true_lens: jax.Array, start_pos: jax.Array, pad_len: int):
        S = cache["k"].shape[2]
        rel = jnp.arange(pad_len)                            # (C,)
        positions = start_pos[:, None] + rel[None, :]        # (B, C)
        sel = (rel[None, :] < true_lens[:, None])[..., None, None]
        # gather-side clamp only: invalid rows may index past S. The
        # scatter below uses the UNCLAMPED positions so out-of-range
        # updates are dropped (jax scatter default) instead of clamping
        # onto S-1 — a clamped duplicate would race the last valid row's
        # write (scatter order with duplicate indices is undefined)
        row_idx = jnp.minimum(positions, S - 1)
        rope_pos = jnp.minimum(positions, c.max_seq - 1)
        bidx = jnp.arange(tokens.shape[0])[:, None]          # (B, 1)

        def attend(q, k, v, state):
            # scatter each slot's window rows at its own offset; in-range
            # invalid rows re-write their current contents, out-of-range
            # rows are dropped (positions unclamped — no duplicates)
            kc, vc = state                                   # (B, S, KV, D)
            kc = kc.at[bidx, positions].set(
                jnp.where(sel, k, kc[bidx, row_idx]).astype(kc.dtype))
            vc = vc.at[bidx, positions].set(
                jnp.where(sel, v, vc[bidx, row_idx]).astype(vc.dtype))
            # attend over each slot's full row set: key j visible to
            # window query i iff j <= start_pos + i
            return (attend_rows(q, kc, vc, positions, c.head_dim ** -0.5),
                    (kc, vc))

        x = embed(params, tokens, c)                         # (B, C, E)
        x, (new_k, new_v) = _scan_cache(attend, x, params, cache, c,
                                        rope_pos)
        # KV-ingest: the caller discards logits — skip the final norm
        # and the (B, C, vocab) head projection entirely
        all_logits = logits_f32(x, params, c) if with_logits else None
        # provisional: start + window length for touched slots; the
        # engine installs the accepted lengths right after
        new_len = jnp.where(true_lens > 0,
                            (start_pos + true_lens).astype(jnp.int32),
                            cache["length"])
        return ({"k": new_k, "v": new_v, "length": new_len}, all_logits)

    return _bind_padded(verify, params, tokens_at=0)


def make_kv_ingest(params: Params, config: LlamaConfig):
    """KV-write-only sibling of :func:`make_batched_spec_verify`: writes
    exactly the same cache rows but skips the final norm + lm-head
    projection, so no ``(slots, C, vocab)`` logits einsum is paid.

    ingest(cache, tokens (B, C), true_lens (B,), start_pos (B,)) → cache

    This is the draft catch-up path (speculation.DraftProposer): after an
    all-K-accepted round the draft cache is one token behind and the
    catch-up only needs the KV rows — reusing the verify program meant
    every such round computed (and discarded) a full-vocab logits block
    (PERF_PLAN round 7, "known draft-path optimization, not yet taken").
    """
    call = make_batched_spec_verify(params, config, with_logits=False)

    def ingest(cache, tokens, true_lens, start_pos):
        return call(cache, tokens, true_lens, start_pos)[0]

    return ingest


def make_inject(config: LlamaConfig):
    """Build the jitted KV-injection step: write an externally computed
    prompt KV (from a prefill replica or a prefix cache) into one slot.

    This is the TPU-native KV-transfer half of prefill/decode
    disaggregation (reference: python/ray/llm/_internal/serve/
    deployments/prefill_decode_disagg/ — there vLLM moves KV via
    NIXL/NCCL; here KV rides the object plane as arrays and lands in the
    slot cache with one dynamic_update_slice per array).

    inject(cache, k, v, true_len, slot) → cache
        k, v: (layers, P, kv_heads, head_dim) padded to a bucket; rows
        at or beyond true_len must be zero (prefill masks them).
    """
    del config

    def inject(cache: Cache, k: jax.Array, v: jax.Array,
               true_len: jax.Array, slot: jax.Array):
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k[:, None].astype(cache["k"].dtype),
            (0, slot, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v[:, None].astype(cache["v"].dtype),
            (0, slot, 0, 0, 0))
        new_len = cache["length"].at[slot].set(true_len)
        return {"k": kc, "v": vc, "length": new_len}

    return jax.jit(inject, donate_argnums=(0,))


def pad_to_bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return ((n + 511) // 512) * 512
