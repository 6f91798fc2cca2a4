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
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import LlamaConfig, Params
from ray_tpu.ops.attention import on_tpu
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

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


def _attend_cached(q, k_cache, v_cache, lengths, scale):
    """q: (B, 1, H, D) new-token queries; k/v_cache: (B, S, KV, D);
    lengths: (B,) valid prefix per slot (incl. the new token).

    Dispatches to the Pallas flash-decoding kernel on TPU; the XLA path
    uses a GROUPED einsum (q reshaped (B,KV,group,D)) so the KV cache is
    never materialized head-repeated — on a (slots, S, KV, D) cache that
    repeat was group x cache-size of wasted HBM traffic per step."""
    B, _, H, D = q.shape
    KV = k_cache.shape[2]
    group = H // KV
    if on_tpu():
        from ray_tpu.ops.pallas.decode_attention import decode_attention

        return decode_attention(q, k_cache, v_cache, lengths, scale=scale)
    qg = q.astype(jnp.float32).reshape(B, KV, group, D)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, kf) * scale     # (B,KV,group,S)
    mask = (jnp.arange(s.shape[-1])[None, :] < lengths[:, None])
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, vf)            # (B,KV,group,D)
    return out.reshape(B, 1, H, D).astype(q.dtype)


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


def _decode_block(x, layer, k_cache, v_cache, lengths, cos, sin,
                  config: LlamaConfig):
    """One transformer block for one new token per slot, updating cache.

    x: (B, 1, E); k/v_cache: (B, S, KV, D); lengths: (B,) count BEFORE
    this token. Returns (x, new_k_cache, new_v_cache).
    """
    c = config
    h = rmsnorm(x, layer["attn_norm"], c.norm_eps)
    q = jnp.einsum("bse,ehd->bshd", h, layer["wq"].astype(h.dtype))
    k = jnp.einsum("bse,ehd->bshd", h, layer["wk"].astype(h.dtype))
    v = jnp.einsum("bse,ehd->bshd", h, layer["wv"].astype(h.dtype))
    positions = lengths[:, None]                           # (B, 1)
    q = apply_rope(q, cos, sin, positions)
    k = apply_rope(k, cos, sin, positions)

    # write new k/v at each slot's current length
    slot_ids = jnp.arange(x.shape[0])
    k_cache = k_cache.at[slot_ids, lengths].set(k[:, 0])
    v_cache = v_cache.at[slot_ids, lengths].set(v[:, 0])

    out = _attend_cached(q, k_cache, v_cache, lengths + 1,
                         c.head_dim ** -0.5)
    x = x + jnp.einsum("bshd,hde->bse", out,
                       layer["wo"].astype(x.dtype))
    h = rmsnorm(x, layer["mlp_norm"], c.norm_eps)
    g = jnp.einsum("bse,em->bsm", h, layer["w_gate"].astype(h.dtype))
    u = jnp.einsum("bse,em->bsm", h, layer["w_up"].astype(h.dtype))
    x = x + jnp.einsum("bsm,me->bse", jax.nn.silu(g) * u,
                       layer["w_down"].astype(h.dtype))
    return x, k_cache, v_cache


def make_decode_step(params: Params, config: LlamaConfig):
    """Build the jitted one-token-for-all-slots decode step.

    step(cache, tokens (B,) int32, active (B,) bool) →
        (cache, logits (B, vocab) f32)
    Inactive slots pass through untouched (their length doesn't advance).
    """
    c = config

    def step(params: Params, cache: Cache, tokens: jax.Array,
             active: jax.Array):
        lengths = cache["length"]
        cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
        x = params["embed"].astype(c.dtype)[tokens][:, None, :]  # (B,1,E)

        def body(x, scanned):
            layer, kc, vc = scanned
            x, kc, vc = _decode_block(x, layer, kc, vc, lengths, cos, sin, c)
            return x, (kc, vc)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        x = rmsnorm(x, params["final_norm"], c.norm_eps)
        head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
        logits = jnp.einsum("be,ev->bv", x[:, 0].astype(jnp.float32),
                            head.astype(jnp.float32))
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
        cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
        x = params["embed"].astype(c.dtype)[tokens]          # (1, P, E)
        positions = jnp.arange(pad_len)[None, :]
        mask_valid = positions[0] < true_len                 # (P,)

        def body(x, scanned):
            layer, kc_all, vc_all = scanned                  # (slots, S, …)
            h = rmsnorm(x, layer["attn_norm"], c.norm_eps)
            q = jnp.einsum("bse,ehd->bshd", h, layer["wq"].astype(h.dtype))
            k = jnp.einsum("bse,ehd->bshd", h, layer["wk"].astype(h.dtype))
            v = jnp.einsum("bse,ehd->bshd", h, layer["wv"].astype(h.dtype))
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
            # causal attention within the prompt
            from ray_tpu.ops.attention import mha_reference

            out = mha_reference(q, k, v, causal=True)
            x = x + jnp.einsum("bshd,hde->bse", out,
                               layer["wo"].astype(x.dtype))
            h2 = rmsnorm(x, layer["mlp_norm"], c.norm_eps)
            g = jnp.einsum("bse,em->bsm", h2,
                           layer["w_gate"].astype(h2.dtype))
            u = jnp.einsum("bse,em->bsm", h2, layer["w_up"].astype(h2.dtype))
            x = x + jnp.einsum("bsm,me->bse", jax.nn.silu(g) * u,
                               layer["w_down"].astype(h2.dtype))
            # write prompt k/v into this slot's cache rows [0, P)
            kc_all = jax.lax.dynamic_update_slice(
                kc_all, jnp.where(mask_valid[None, :, None, None], k,
                                  0.0).astype(kc_all.dtype),
                (slot, 0, 0, 0))
            vc_all = jax.lax.dynamic_update_slice(
                vc_all, jnp.where(mask_valid[None, :, None, None], v,
                                  0.0).astype(vc_all.dtype),
                (slot, 0, 0, 0))
            return x, (kc_all, vc_all)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        x = rmsnorm(x, params["final_norm"], c.norm_eps)
        last = x[0, jnp.maximum(true_len - 1, 0)]
        head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
        logits = (last.astype(jnp.float32) @ head.astype(jnp.float32))
        new_len = cache["length"].at[slot].set(true_len)
        return ({"k": new_k, "v": new_v, "length": new_len}, logits)

    def call(cache, tokens, true_len, slot):
        pad_len = tokens.shape[1]
        return prefill(params, cache, tokens,
                       jnp.asarray(true_len, jnp.int32),
                       jnp.asarray(slot, jnp.int32), pad_len=pad_len)

    call.jitted = prefill
    return call


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
        S = cache["k"].shape[2]
        cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
        x = params["embed"].astype(c.dtype)[tokens]          # (1, C, E)
        rel = jnp.arange(pad_len)                            # (C,)
        positions = (start_pos + rel)[None, :]               # (1, C)
        mask_valid = rel < true_len                          # (C,)

        def body(x, scanned):
            layer, kc_all, vc_all = scanned                  # (slots, S, …)
            h = rmsnorm(x, layer["attn_norm"], c.norm_eps)
            q = jnp.einsum("bse,ehd->bshd", h, layer["wq"].astype(h.dtype))
            k = jnp.einsum("bse,ehd->bshd", h, layer["wk"].astype(h.dtype))
            v = jnp.einsum("bse,ehd->bshd", h, layer["wv"].astype(h.dtype))
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
            # write the chunk's k/v at rows [start_pos, start_pos + C)
            kc_all = jax.lax.dynamic_update_slice(
                kc_all, jnp.where(mask_valid[None, :, None, None], k,
                                  0.0).astype(kc_all.dtype),
                (slot, start_pos, 0, 0))
            vc_all = jax.lax.dynamic_update_slice(
                vc_all, jnp.where(mask_valid[None, :, None, None], v,
                                  0.0).astype(vc_all.dtype),
                (slot, start_pos, 0, 0))
            # attend over the slot's FULL row set (prefix + this chunk):
            # key j visible to query i iff j <= start_pos + i
            ks = kc_all[slot]                                # (S, KV, D)
            vs = vc_all[slot]
            KV = ks.shape[1]
            H = q.shape[2]
            group = H // KV
            qg = (q[0].astype(jnp.float32)
                  .reshape(pad_len, KV, group, -1))          # (C,KV,g,D)
            s = jnp.einsum("ckgd,skd->kgcs", qg,
                           ks.astype(jnp.float32)) * (c.head_dim ** -0.5)
            allowed = (jnp.arange(S)[None, :]
                       <= (start_pos + rel)[:, None])        # (C, S)
            s = jnp.where(allowed[None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("kgcs,skd->ckgd", p,
                             vs.astype(jnp.float32))
            out = out.reshape(1, pad_len, H, -1).astype(x.dtype)
            x = x + jnp.einsum("bshd,hde->bse", out,
                               layer["wo"].astype(x.dtype))
            h2 = rmsnorm(x, layer["mlp_norm"], c.norm_eps)
            g = jnp.einsum("bse,em->bsm", h2,
                           layer["w_gate"].astype(h2.dtype))
            u = jnp.einsum("bse,em->bsm", h2, layer["w_up"].astype(h2.dtype))
            x = x + jnp.einsum("bsm,me->bse", jax.nn.silu(g) * u,
                               layer["w_down"].astype(h2.dtype))
            return x, (kc_all, vc_all)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        x = rmsnorm(x, params["final_norm"], c.norm_eps)
        last = x[0, jnp.maximum(true_len - 1, 0)]
        head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
        logits = (last.astype(jnp.float32) @ head.astype(jnp.float32))
        new_len = cache["length"].at[slot].set(start_pos + true_len)
        return ({"k": new_k, "v": new_v, "length": new_len}, logits)

    def call(cache, tokens, true_len, start_pos, slot):
        pad_len = tokens.shape[1]
        return chunk(params, cache, tokens,
                     jnp.asarray(true_len, jnp.int32),
                     jnp.asarray(start_pos, jnp.int32),
                     jnp.asarray(slot, jnp.int32), pad_len=pad_len)

    call.jitted = chunk
    return call


def make_batched_spec_verify(params: Params, config: LlamaConfig):
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
    are invisible."""
    return _make_window_forward(params, config, with_logits=True)


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
    call = _make_window_forward(params, config, with_logits=False)

    def ingest(cache, tokens, true_lens, start_pos):
        cache, _ = call(cache, tokens, true_lens, start_pos)
        return cache

    return ingest


def _make_window_forward(params: Params, config: LlamaConfig,
                         with_logits: bool):
    """Shared builder: per-slot token windows scattered at per-slot
    offsets through the full stack, with (``with_logits``) or without the
    lm-head projection.  See :func:`make_batched_spec_verify` for the
    window semantics."""
    c = config

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("pad_len",))
    def verify(params: Params, cache: Cache, tokens: jax.Array,
               true_lens: jax.Array, start_pos: jax.Array, pad_len: int):
        S = cache["k"].shape[2]
        B = tokens.shape[0]
        cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
        x = params["embed"].astype(c.dtype)[tokens]          # (B, C, E)
        rel = jnp.arange(pad_len)                            # (C,)
        positions = start_pos[:, None] + rel[None, :]        # (B, C)
        valid = rel[None, :] < true_lens[:, None]            # (B, C)
        # gather-side clamp only: invalid rows may index past S. The
        # scatter below uses the UNCLAMPED positions so out-of-range
        # updates are dropped (jax scatter default) instead of clamping
        # onto S-1 — a clamped duplicate would race the last valid row's
        # write (scatter order with duplicate indices is undefined)
        row_idx = jnp.minimum(positions, S - 1)
        rope_pos = jnp.minimum(positions, cos.shape[0] - 1)
        bidx = jnp.arange(B)[:, None]                        # (B, 1)

        def body(x, scanned):
            layer, kc, vc = scanned                          # (B, S, KV, D)
            h = rmsnorm(x, layer["attn_norm"], c.norm_eps)
            q = jnp.einsum("bse,ehd->bshd", h, layer["wq"].astype(h.dtype))
            k = jnp.einsum("bse,ehd->bshd", h, layer["wk"].astype(h.dtype))
            v = jnp.einsum("bse,ehd->bshd", h, layer["wv"].astype(h.dtype))
            q = apply_rope(q, cos, sin, rope_pos)
            k = apply_rope(k, cos, sin, rope_pos)
            # scatter each slot's window rows at its own offset; in-range
            # invalid rows re-write their current contents, out-of-range
            # rows are dropped (positions unclamped — no duplicates)
            old_k = kc[bidx, row_idx]                        # (B, C, KV, D)
            old_v = vc[bidx, row_idx]
            sel = valid[..., None, None]
            kc = kc.at[bidx, positions].set(
                jnp.where(sel, k, old_k).astype(kc.dtype))
            vc = vc.at[bidx, positions].set(
                jnp.where(sel, v, old_v).astype(vc.dtype))
            # attend over the slot's full row set: key j visible to
            # window query i iff j <= start_pos + i (grouped einsum, KV
            # never head-repeated — same layout as _attend_cached)
            KV = kc.shape[2]
            H = q.shape[2]
            group = H // KV
            qg = (q.astype(jnp.float32)
                  .reshape(B, pad_len, KV, group, -1))       # (B,C,KV,g,D)
            s = jnp.einsum("bckgd,bskd->bkgcs", qg,
                           kc.astype(jnp.float32)) * (c.head_dim ** -0.5)
            allowed = (jnp.arange(S)[None, None, :]
                       <= positions[:, :, None])             # (B, C, S)
            s = jnp.where(allowed[:, None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bkgcs,bskd->bckgd", p,
                             vc.astype(jnp.float32))
            out = out.reshape(B, pad_len, H, -1).astype(x.dtype)
            x = x + jnp.einsum("bshd,hde->bse", out,
                               layer["wo"].astype(x.dtype))
            h2 = rmsnorm(x, layer["mlp_norm"], c.norm_eps)
            g = jnp.einsum("bse,em->bsm", h2,
                           layer["w_gate"].astype(h2.dtype))
            u = jnp.einsum("bse,em->bsm", h2, layer["w_up"].astype(h2.dtype))
            x = x + jnp.einsum("bsm,me->bse", jax.nn.silu(g) * u,
                               layer["w_down"].astype(h2.dtype))
            return x, (kc, vc)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        if with_logits:
            x = rmsnorm(x, params["final_norm"], c.norm_eps)
            head = (params["embed"].T if c.tie_embeddings
                    else params["lm_head"])
            all_logits = jnp.einsum("bce,ev->bcv", x.astype(jnp.float32),
                                    head.astype(jnp.float32))
        else:
            # KV-ingest: the caller discards logits — skip the final norm
            # and the (B, C, vocab) head projection entirely
            all_logits = None
        # provisional: start + window length for touched slots; the
        # engine installs the accepted lengths right after
        new_len = jnp.where(true_lens > 0,
                            (start_pos + true_lens).astype(jnp.int32),
                            cache["length"])
        return ({"k": new_k, "v": new_v, "length": new_len}, all_logits)

    def call(cache, tokens, true_lens, start_pos):
        pad_len = tokens.shape[1]
        return verify(params, cache, tokens,
                      jnp.asarray(true_lens, jnp.int32),
                      jnp.asarray(start_pos, jnp.int32), pad_len=pad_len)

    call.jitted = verify
    return call


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


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0        # 0 → greedy
    eos_token: Optional[int] = None


def sample_token(logits, temperature: float, key) -> Tuple[jax.Array, any]:
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), key
    key, sub = jax.random.split(key)
    tok = jax.random.categorical(sub, logits / temperature, axis=-1)
    return tok.astype(jnp.int32), key
