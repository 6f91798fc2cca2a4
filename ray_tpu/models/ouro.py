"""A dense decoder whose layers RUN SEVERAL TIMES a token (the looped
language model of the ``ouro`` family), on the paged serving path.

With ``T = total_ut_steps`` passes over the same ``L`` layers, ``q =
early_exit_threshold`` and ``RMS(x; g)`` the RMSNorm that scales by ``1 +
g``::

    h_0 = E[tokens]
    for t in 0 .. T-1:
        x = h_t
        for l in 0 .. L-1:                  # layer l's weights, every pass
            x = x + RMS(Attention_l(RMS(x; g1_l)); g2_l)
            x = x + RMS(MLP_l(RMS(x; g3_l)); g4_l)
        h_{t+1} = RMS(x; g_final)           # the next pass's input
        lambda_t = sigmoid(w_exit . h_{t+1} + b_exit)
    p_t = lambda_t prod_{s<t} (1 - lambda_s)  (t < T-1),  p_{T-1} the rest
    t* = the first t with p_0 + .. + p_t >= q, else T-1       # a position
    logits = W_head h_{t*+1}

The block's arithmetic is the dense decoder's (:func:`llama.qkv`,
:func:`llama.mlp`: 16 MHA heads, rotary, SwiGLU, no bias) between FOUR
norms. Every pass runs for every position whatever the gate says, so
every pass's cache is whole: the keys and values of pass ``t``, layer
``l`` are rows of their own at pool index ``t * L + l``, and a query of
pass ``t`` attends over pass ``t``'s rows only. The pool is the dense
paged one, ``(T * L, blocks, block size, KV * D)``, ``T * L`` deep under
``L`` layers of weights, one donated buffer that ONE scan over the ``T *
L`` pool layers carries whole and updates in place;
the decode kernel is handed the pool and the index.

Two programs (:mod:`ray_tpu.models.serving`: ``paged``), each around the
dense decoder's pieces: a prefill and a decode step for all slots. The
head runs once a program, on the state the gate selected.
``cache["counters"]`` is the exit distribution summed over a program's
running rows (:func:`counter_names`). The model brings no other builder:
the engine refuses the slot cache, chunked prefill, speculation, prefix
reuse and KV transfer by name. Its ``place`` is the dense decoder's: the
block's projections are :func:`llama.qkv`'s.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.decoding import _bind_padded, _bind_params
from ray_tpu.models.llama import (embed, logits_f32, mlp, qkv,
                                  serving_layout)
from ray_tpu.models.paged_cache import (BlockAllocator, PagedConfig,
                                        _decode_work, fold_heads,
                                        store_kv_rows)
from ray_tpu.ops.attention import mha_reference
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.pallas.paged_decode_attention import paged_decode
from ray_tpu.ops.rope import rope_frequencies
from ray_tpu.util.profiling import part

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """The fields the dense decoder's pieces read, under their names
    there, and the loop's two."""

    vocab_size: int = 256
    hidden: int = 64
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 16
    mlp_dim: int = 128
    max_seq: int = 512
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"query heads {self.n_heads} not a multiple "
                             f"of the {self.n_kv_heads} KV heads")
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps {self.total_ut_steps}: the "
                             "layers run once at least")

    @property
    def pool_layers(self) -> int:
        """Layers of KV state: one for each pass of each layer."""
        return self.total_ut_steps * self.n_layers

    def serving_model(self):
        return OuroServing(self)


def counter_names(cfg: OuroConfig) -> Tuple[str, ...]:
    """``exit_p<t>``: the sum over a program's running rows of the
    probability of leaving after pass ``t``; ``exit_rows``: the rows."""
    return tuple(f"exit_p{t}" for t in range(cfg.total_ut_steps)) + (
        "exit_rows",)


# ----------------------------------------------------------------- weights
def param_shapes(cfg: OuroConfig) -> Params:
    """The tree the builders take, as shapes: ``layers`` stacked on a
    leading axis as the dense decoder's are. A norm's stored weight ``w``
    scales by ``1 + w``."""
    c = cfg
    L, h, m = c.n_layers, c.hidden, c.mlp_dim
    H, KV, D = c.n_heads, c.n_kv_heads, c.head_dim
    return {"embed": (c.vocab_size, h),
            "layers": {
                "attn_norm": (L, h), "wq": (L, h, H, D), "wk": (L, h, KV, D),
                "wv": (L, h, KV, D), "wo": (L, H, D, h),
                "attn_post_norm": (L, h), "mlp_norm": (L, h),
                "w_gate": (L, h, m), "w_up": (L, h, m), "w_down": (L, m, h),
                "mlp_post_norm": (L, h)},
            "final_norm": (h,), "exit_w": (h,), "exit_b": (1,),
            "lm_head": (h, c.vocab_size)}


LOOP_SCALE = 6.0


def param_stds(cfg: OuroConfig):
    """(default standard deviation, {leaf name: its own}). The embedding
    and the final norm are drawn ``LOOP_SCALE`` times wider than what a
    sublayer adds to the stream (and the gate's vector as much
    narrower): every pass then starts from a state that a sublayer's
    unit vector turns little, and the seeded loop contracts. At 1 it
    expands on some seeds, and rounding grows from pass to pass."""
    std = cfg.hidden ** -0.5
    return std, {
        "attn_norm": 0.1, "attn_post_norm": 0.1, "mlp_norm": 0.1,
        "mlp_post_norm": 0.1, "final_norm": LOOP_SCALE,
        "embed": LOOP_SCALE, "exit_w": std / LOOP_SCALE, "exit_b": 0.5}


def init_params(cfg: OuroConfig, key: jax.Array) -> Params:
    from ray_tpu.models.serving import init_from_shapes

    return init_from_shapes(param_shapes(cfg), key, *param_stds(cfg),
                            cfg.dtype)


# ------------------------------------------------------------------- cache
def init_cache(cfg: OuroConfig, page: PagedConfig, num_slots: int):
    shape = (cfg.pool_layers, page.num_blocks, page.block_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "length": jnp.zeros((num_slots,), jnp.int32),
            "counters": jnp.zeros((len(counter_names(cfg)),), jnp.float32)}


def make_page(*, max_seq: int, block_size: int, pool_tokens: int
              ) -> PagedConfig:
    return PagedConfig(num_blocks=1 + -(-pool_tokens // block_size),
                       block_size=block_size, max_seq=max_seq)  # + null


# ---------------------------------------------------------------- the loop
def loop_block(x, layer, c: OuroConfig, cos, sin, positions, attend, state):
    """One layer of one pass: :func:`decoding.dense_block` with a norm
    after each sublayer as well as before it. x (B, S, E) -> (x, state);
    ``attend`` and ``state`` as there."""
    with part("attn_proj"):
        q, k, v = qkv(rmsnorm(x, layer["attn_norm"], c.norm_eps), layer,
                      cos, sin, positions)
    out, state = attend(q, k, v, state)
    with part("attn_proj"):
        a = jnp.einsum("bshd,hde->bse", out, layer["wo"].astype(x.dtype))
    with part("post_norm"):
        x = x + rmsnorm(a, layer["attn_post_norm"], c.norm_eps)
    with part("mlp"):
        m = mlp(rmsnorm(x, layer["mlp_norm"], c.norm_eps), layer)
    with part("post_norm"):
        x = x + rmsnorm(m, layer["mlp_post_norm"], c.norm_eps)
    return x, state


def _run_passes(attend, x, params: Params, cache, c: OuroConfig, cos, sin,
                positions, row):
    """The ``T`` passes over the ``L`` layers as ONE scan over the ``T *
    L`` pool layers: step ``i`` runs layer ``i % L``'s weights on pool
    layer ``i`` (``attend``'s ``state = (k_pool, v_pool, i)`` in and
    ``(k_pool, v_pool)`` out), and after a pass's last layer the norm
    between passes and the gate, which reads ``x[row]`` (a decode step's
    every slot, a prefill's last valid position). Both pools ride in the
    carry and are updated in place, as in
    :func:`paged_cache._scan_layers`.

    One loop, because the chip's compiler lays ``wq`` and ``wk`` out
    anew, whole, ahead of the loops as soon as TWO loops read the same
    stacked weights (a scan of passes around the scan of layers, or the
    passes written out one after another): 0.75 GiB of temporaries at
    the published sizes for a step that is no shorter (46.7 against 47.0
    ms on the chip). A single loop re-lays a layer's slice at a time, as
    the dense decoder's scan does. -> (states (T, ..., E): each pass's
    ``x[row]`` BEFORE the norm between passes, lambdas (T, ...) float32,
    k_pool, v_pool)."""
    L, T = c.n_layers, c.total_ut_steps

    def between(x, states, lams, t):
        with part("exit_gate"):
            h = rmsnorm(x, params["final_norm"], c.norm_eps)
            lam = jax.nn.sigmoid(
                h[row].astype(jnp.float32) @ params["exit_w"].astype(
                    jnp.float32) + params["exit_b"].astype(jnp.float32)[0])
            return h, states.at[t].set(x[row]), lams.at[t].set(lam)

    def body(carry, i):
        x, kc, vc, states, lams = carry
        layer = jax.tree.map(
            lambda w: jax.lax.dynamic_index_in_dim(w, i % L, keepdims=False),
            params["layers"])
        x, (kc, vc) = loop_block(x, layer, c, cos, sin, positions, attend,
                                 (kc, vc, i))
        x, states, lams = jax.lax.cond(
            i % L == L - 1, between, lambda x, s, lm, t: (x, s, lm),
            x, states, lams, i // L)
        return (x, kc, vc, states, lams), None

    seen = x[row]
    (_, kc, vc, states, lams), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"],
               jnp.zeros((T,) + seen.shape, x.dtype),
               jnp.zeros((T,) + seen.shape[:-1], jnp.float32)),
        jnp.arange(T * L, dtype=jnp.int32))
    return states, lams, kc, vc


@part("exit_gate")
def exit_distribution(lams):
    """lambdas (T, ...) -> p (T, ...): ``p_t = lambda_t prod_{s<t} (1 -
    lambda_s)`` and the last pass takes what is left."""
    stay = jnp.cumprod(1.0 - lams, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(lams * before)[:-1], before[-1:]])


@part("exit_gate")
def exit_pass(p, threshold: float):
    """The first pass at which the cumulated probability reaches
    ``threshold``, else the last: p (T, ...) -> (...) int32."""
    reached = jnp.cumsum(p, axis=0) >= threshold
    return jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0),
                     p.shape[0] - 1).astype(jnp.int32)


def _head(states, lams, params: Params, c: OuroConfig, weight):
    """The selected pass's logits and the program's counters. states
    (T, ..., E), lams (T, ...), ``weight`` (...): 1 for a running row."""
    p = exit_distribution(lams)
    with part("exit_gate"):
        chosen = exit_pass(p, c.early_exit_threshold)
        x = jnp.take_along_axis(states, chosen[None, ..., None], axis=0)[0]
        w = weight.astype(jnp.float32)
        counters = jnp.concatenate([
            (p * w).reshape(p.shape[0], -1).sum(axis=1), w.sum()[None]])
    # the norm between passes over the chosen pass's state IS h_{t*+1}:
    # the head's own final norm computes it again, on one row a slot
    return logits_f32(x, params, c), counters


# ---------------------------------------------------------------- programs
def _rope_table(c: OuroConfig, page: PagedConfig):
    return rope_frequencies(c.head_dim,
                            page.max_blocks_per_seq * page.block_size,
                            c.rope_theta)


def make_decode_step(params: Params, cfg: OuroConfig, page: PagedConfig):
    """step(cache, tables (B, MBS) i32, tokens (B,) i32, active (B,)
    bool) -> (cache, logits (B, vocab) f32), as
    :func:`paged_cache.make_paged_decode_step`: every pass writes ONE row
    a slot into each of its ``L`` pool layers and attends over them; the
    work list is built once for all ``T * L`` kernel calls."""
    c, bs = cfg, page.block_size

    def step(params: Params, cache, tables, tokens, active):
        lengths = cache["length"]
        slot_rows = jnp.arange(tokens.shape[0])
        with part("kv_store"):
            blk = jnp.where(active, tables[slot_rows, lengths // bs], 0)
            off = lengths % bs
            att_len = jnp.where(active, lengths + 1, 0)
        work = _decode_work(att_len, page)

        def attend(q, k, v, state):
            kc, vc, i = state
            kc, vc = store_kv_rows((kc, vc), (i, blk, off),
                                   fold_heads(k[:, 0]), fold_heads(v[:, 0]))
            out = paged_decode(q, kc, vc, i, tables, att_len,
                               scale=c.head_dim ** -0.5, work=work)
            return out, (kc, vc)

        x = embed(params, tokens, c)[:, None, :]                  # (B,1,E)
        cos, sin = _rope_table(c, page)
        states, lams, new_k, new_v = _run_passes(
            attend, x, params, cache, c, cos, sin, lengths[:, None],
            (slice(None), 0))
        logits, counters = _head(states, lams, params, c, active)
        new_len = jnp.where(active, lengths + 1, lengths)
        return ({"k": new_k, "v": new_v, "length": new_len,
                 "counters": counters}, logits)

    return _bind_params(jax.jit(step, donate_argnums=(1,)), params)


def make_prefill(params: Params, cfg: OuroConfig, page: PagedConfig):
    """prefill(cache, table_row (MBS,) i32, tokens (1, P) padded,
    true_len, slot) -> (cache, last_logits (vocab,) f32), as
    :func:`paged_cache.make_paged_prefill`: every pass is causal within
    the prompt and fills whole blocks of its own ``L`` pool layers."""
    c, bs = cfg, page.block_size

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("pad_len",))
    def prefill(params: Params, cache, table_row, tokens, true_len, slot,
                pad_len: int):
        nblk = pad_len // bs
        blocks_shape = (nblk, bs, c.n_kv_heads * c.head_dim)
        positions = jnp.arange(pad_len)[None, :]
        mask_valid = positions[0] < true_len                  # (P,)
        with part("kv_store"):
            dest = jnp.where(jnp.arange(nblk) * bs < true_len,
                             table_row[:nblk], 0)              # (nblk,)

        def attend(q, k, v, state):
            kc, vc, i = state
            with part("kv_store"):
                kb = jnp.where(mask_valid[:, None, None], k[0],
                               0.0).reshape(blocks_shape)
                vb = jnp.where(mask_valid[:, None, None], v[0],
                               0.0).reshape(blocks_shape)
            pools = store_kv_rows((kc, vc), (i, dest), kb, vb)
            return mha_reference(q, k, v, causal=True), pools

        x = embed(params, tokens, c)                          # (1, P, E)
        cos, sin = _rope_table(c, page)
        states, lams, new_k, new_v = _run_passes(
            attend, x, params, cache, c, cos, sin, positions,
            (0, jnp.maximum(true_len - 1, 0)))
        logits, counters = _head(states, lams, params, c, jnp.ones(()))
        new_len = cache["length"].at[slot].set(true_len)
        return ({"k": new_k, "v": new_v, "length": new_len,
                 "counters": counters}, logits)

    return _bind_padded(prefill, params, tokens_at=1, multiple_of=bs)


# ------------------------------------------------- what the engine is given
class OuroServing:
    """The model as :class:`ray_tpu.serve.llm.LLMEngine` takes it
    (:mod:`ray_tpu.models.serving`): the paged cache, the prefill and the
    decode step, no other builder, and where its weights lie."""

    def __init__(self, config: OuroConfig):
        self.config = config

    def init_params(self, key):
        return init_params(self.config, key)

    def place(self, params):
        return serving_layout(params)        # the block's is llama.qkv

    def paged(self, params, *, num_slots: int, max_seq: int,
              block_size: int, pool_tokens: int):
        from ray_tpu.models.serving import PagedPrograms

        page = make_page(max_seq=max_seq, block_size=block_size,
                         pool_tokens=pool_tokens)
        return PagedPrograms(
            alloc=BlockAllocator(page, num_slots),
            cache=init_cache(self.config, page, num_slots),
            prefill=make_prefill(params, self.config, page),
            decode=make_decode_step(params, self.config, page),
            page=page, counters=counter_names(self.config))
