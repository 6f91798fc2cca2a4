"""A decoder with latent (multi-head latent, "MLA") attention in every
layer and a routed expert MLP with a shared expert and group-limited
routing (the language model of the ``axk1`` family), on the paged
serving path.

Attention, per layer (``H`` heads; ``nope`` + ``rope`` wide queries and
keys, ``v`` wide values; ``scale`` = ``(nope + rope) ** -0.5`` times
YaRN's factor)::

    c_q            = rmsnorm(x W_qa)                    q_rank wide
    [q_nope; q_r]  = c_q W_qb                           H x (nope + rope)
    [c_kv; k_r]    = x W_kva                            kv_rank + rope
    c_kv           = rmsnorm(c_kv)
    q_rope, k_rope = rope(q_r), rope(k_r)               k_rope: ONE row
    k_nope_h, v_h  = c_kv W_kvb_k[h], c_kv W_kvb_v[h]
    z_h[i, j]      = scale (q_nope_h[i] . k_nope_h[j] + q_rope_h[i] . k_rope[j])

What is cached is the latent row ``(c_kv, k_rope)``: the third kind of
KV state of :class:`ray_tpu.models.paged_cache.KVStateManager`
(``"latent"``, keeps the whole sequence), ONE pool ``(L, NB, bs, W)``
with ``W`` = ``kv_rank + rope`` rounded up to whole lanes (576 -> 640 at
the published widths: the chip's tiled layout pads a 576-wide row to 640
whatever the shape says, so a token costs 1,280 B a layer of which 1,152
are read for a reason). The pool is one donated buffer that every layer
updates in place.

Prefill is EXPANDED (``mla_expand``): keys and values of the prompt are
made from its latents and attention runs over the prompt itself. Decode
is ABSORBED (``mla_absorb``): ``q~_h = q_nope_h W_kvb_k[h]^T``, the
kernel (:mod:`ray_tpu.ops.pallas.paged_mla_decode_attention`) gives
``u_h = sum_j p_h[j] c_kv[j]``, and ``o_h = u_h W_kvb_v[h]``: all heads
read one row a token. ``W_kvb`` is STORED in its two halves
(``w_kvb_k``, ``w_kvb_v``): the same bytes as the published single
matrix, and no slice of it is taken in a step.

The MLP: the first ``dense_layers`` layers a dense SwiGLU; the others
``shared(x) + routed(x)`` with :func:`ray_tpu.models.moe.shared_expert`
(whole on every chip) and :func:`ray_tpu.models.moe.experts_by_share`
(sigmoid scores, ``n_group`` groups of which ``topk_group`` are kept,
``top_k`` chosen, no stored correction bias).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import moe
from ray_tpu.models.decoding import _bind_params
from ray_tpu.models.paged_cache import KVStateManager, PagedConfig
from ray_tpu.ops.attention import prompt_attention
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.pallas import paged_mla_decode_attention as mla
from ray_tpu.ops.rope import YarnScaling, apply_rope, rope_frequencies
from ray_tpu.util.profiling import part

Params = Dict[str, Any]
KIND = "latent"


@dataclasses.dataclass(frozen=True)
class AxK1Config:
    vocab_size: int = 256
    hidden: int = 64
    n_layers: int = 3
    n_heads: int = 4
    q_rank: int = 24
    kv_rank: int = 16               # narrower than n_heads * v_dim
    nope_dim: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    rope_theta: float = 1e4
    yarn: Optional[YarnScaling] = YarnScaling(
        factor=4.0, original_max_seq=64, mscale=1.0, mscale_all_dim=1.0)
    dense_layers: int = 1           # leading layers with a dense SwiGLU
    mlp_dim: int = 128
    expert_dim: int = 32
    shared_dim: int = 32            # n_shared_experts * expert width
    n_experts: int = 32             # the router's width
    n_group: int = 8
    topk_group: int = 4
    top_k: int = 8
    experts_held: Tuple[int, int] = (0, 32)      # (first, count) here
    routed_scale: float = 2.5
    norm_eps: float = 1e-6
    max_seq: int = 2048
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_experts % self.n_group:
            raise ValueError("n_experts is not a multiple of n_group")
        if self.rope_dim % 2:
            raise ValueError("rope_dim must be even")

    @property
    def row_width(self) -> int:
        """A latent row in the pool: (c_kv, k_rope) in whole lanes."""
        return mla.padded_row(self.kv_rank + self.rope_dim)

    @property
    def scale(self) -> float:
        s = (self.nope_dim + self.rope_dim) ** -0.5
        return s * (self.yarn.attention_factor if self.yarn else 1.0)

    def serving_model(self):
        return AxK1Serving(self)


# ----------------------------------------------------------------- weights
def param_shapes(cfg: AxK1Config) -> Params:
    """The tree the builders take, as shapes: ``layers`` is a LIST (layer
    0 is not like the others). A norm's stored weight ``w`` scales by
    ``1 + w``; the router is read in float32."""
    c = cfg
    h, H, G = c.hidden, c.n_heads, c.experts_held[1]
    layers = []
    for l in range(c.n_layers):
        layer = {"attn_norm": (h,), "w_qa": (h, c.q_rank),
                 "q_norm": (c.q_rank,),
                 "w_qb": (c.q_rank, H, c.nope_dim + c.rope_dim),
                 "w_kva": (h, c.kv_rank + c.rope_dim),
                 "kv_norm": (c.kv_rank,),
                 "w_kvb_k": (c.kv_rank, H, c.nope_dim),
                 "w_kvb_v": (c.kv_rank, H, c.v_dim),
                 "wo": (H, c.v_dim, h), "mlp_norm": (h,)}
        if l < c.dense_layers:
            layer.update(w_gate=(h, c.mlp_dim), w_up=(h, c.mlp_dim),
                         w_down=(c.mlp_dim, h))
        else:
            layer.update(router=(h, c.n_experts),
                         ws_gate=(h, c.shared_dim), ws_up=(h, c.shared_dim),
                         ws_down=(c.shared_dim, h),
                         we_gate=(G, h, c.expert_dim),
                         we_up=(G, h, c.expert_dim),
                         we_down=(G, c.expert_dim, h))
        layers.append(layer)
    return {"embed": (c.vocab_size, h), "layers": layers,
            "final_norm": (h,), "lm_head": (h, c.vocab_size)}


def param_stds(cfg: AxK1Config):
    """(default standard deviation, {leaf name: its own}): a matrix at
    its fan-in ** -0.5, every projection back into the residual stream
    scaled down by ``sqrt(2 L)`` (a routed expert's by ``routed_scale``
    too, which multiplies the routed sum), norm weights at 0.1."""
    std = cfg.hidden ** -0.5
    out = std / (2 * cfg.n_layers) ** 0.5
    return std, {"attn_norm": 0.1, "mlp_norm": 0.1, "final_norm": 0.1,
                 "q_norm": 0.1, "kv_norm": 0.1,
                 "w_qb": cfg.q_rank ** -0.5,
                 "w_kvb_k": cfg.kv_rank ** -0.5,
                 "w_kvb_v": cfg.kv_rank ** -0.5,
                 "wo": out, "w_down": out, "ws_down": out,
                 "we_down": out / cfg.routed_scale}


def init_params(cfg: AxK1Config, key: jax.Array) -> Params:
    from ray_tpu.models.serving import init_from_shapes

    return init_from_shapes(param_shapes(cfg), key, *param_stds(cfg),
                            cfg.dtype)


# ------------------------------------------------------------------- cache
def page_of(*, max_seq: int, block_size: int, pool_tokens: int
            ) -> PagedConfig:
    return PagedConfig(num_blocks=1 + -(-pool_tokens // block_size),
                       block_size=block_size, max_seq=max_seq)     # + null


def init_cache(cfg: AxK1Config, page: PagedConfig, num_slots: int):
    return {"length": jnp.zeros((num_slots,), jnp.int32),
            "counters": jnp.zeros((len(moe.COUNTERS),), jnp.float32),
            KIND: jnp.zeros((cfg.n_layers, page.num_blocks, page.block_size,
                             cfg.row_width), cfg.dtype)}


def make_manager(page: PagedConfig, num_slots: int) -> KVStateManager:
    return KVStateManager({KIND: (page, None)}, num_slots)


# ------------------------------------------------------------------ blocks
def _rope_tables(cfg: AxK1Config, length: int):
    return rope_frequencies(cfg.rope_dim, length, cfg.rope_theta,
                            yarn=cfg.yarn)


@part("attn_proj")
def _latents(x, layer, cfg, cos, sin, positions):
    """x (B, S, h) -> q_nope (B, S, H, nope), q_rope (B, S, H, rope),
    c_kv (B, S, kv_rank), k_rope (B, S, rope): what both forms of the
    attention start from."""
    h = rmsnorm(x, layer["attn_norm"], cfg.norm_eps)
    c_q = rmsnorm(h @ layer["w_qa"].astype(h.dtype), layer["q_norm"],
                  cfg.norm_eps)
    q = jnp.einsum("bsr,rhd->bshd", c_q, layer["w_qb"].astype(h.dtype))
    kv = h @ layer["w_kva"].astype(h.dtype)
    c_kv = rmsnorm(kv[..., :cfg.kv_rank], layer["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, cfg.kv_rank:], cos, sin, positions)
    q_rope = apply_rope(q[..., cfg.nope_dim:], cos, sin, positions)
    return q[..., :cfg.nope_dim], q_rope, c_kv, k_rope[..., 0, :]


def _rows(c_kv, k_rope, cfg):
    """(..., kv_rank), (..., rope) -> (..., W) pool rows."""
    pad = cfg.row_width - cfg.kv_rank - cfg.rope_dim
    return jnp.concatenate(
        [c_kv, k_rope, jnp.zeros((*c_kv.shape[:-1], pad), c_kv.dtype)], -1)


@part("kv_store")
def _store(pool, where, rows):
    """Write latent rows into the pool at ``where`` (layer, blocks[,
    offsets]): in place, inside a jitted program whose donated pool this
    is."""
    return pool.at[where].set(rows.astype(pool.dtype))


@part("mla_expand")
def attend_expanded(q_nope, q_rope, c_kv, k_rope, layer, cfg):
    """Causal attention over the sequence itself with keys and values
    made from its latents: (B, S, H, v_dim)."""
    dt = c_kv.dtype
    k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, layer["w_kvb_k"].astype(dt))
    v = jnp.einsum("bsr,rhd->bshd", c_kv, layer["w_kvb_v"].astype(dt))
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :], (*k_nope.shape[:-1], cfg.rope_dim))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    return prompt_attention(q, k, v, scale=cfg.scale)


def absorbed_queries(q_nope, q_rope, layer, cfg):
    """(B, H, nope), (B, H, rope) -> (B, H, W): ``W_kvb_k`` folded into
    the query, laid out like a pool row."""
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope,
                       layer["w_kvb_k"].astype(q_nope.dtype))
    return _rows(q_abs, q_rope, cfg)


@part("mla_absorb")
def attend_absorbed(q_nope, q_rope, pool, li, tables, att_len, layer, cfg,
                    work=None):
    """One query a slot over the paged latent rows: (B, H, v_dim)."""
    u = mla.paged_mla_decode(
        absorbed_queries(q_nope, q_rope, layer, cfg), pool, li, tables,
        att_len, scale=cfg.scale, rank=cfg.kv_rank, work=work)
    return jnp.einsum("bhr,rhd->bhd", u, layer["w_kvb_v"].astype(u.dtype))


def _mlp(x, layer, cfg, valid, kernel_name="grouped_expert_matmul"):
    """x (T, h) after the MLP norm -> ((T, h) in x.dtype, counters)."""
    if "router" in layer:
        y, counters = moe.experts_by_share(
            x, layer, experts_held=cfg.experts_held, top_k=cfg.top_k,
            scale=cfg.routed_scale, valid=valid, kernel_name=kernel_name,
            n_group=cfg.n_group, topk_group=cfg.topk_group)
        return (moe.shared_expert(x, layer) + y).astype(x.dtype), counters
    with part("mlp"):
        return (moe.swiglu(x, layer["w_gate"], layer["w_up"],
                           layer["w_down"]),
                jnp.zeros((len(moe.COUNTERS),), jnp.float32))


@part("head")
def _head(x, params, cfg):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)


# ---------------------------------------------------------------- programs
def make_decode_step(params: Params, cfg: AxK1Config, page: PagedConfig):
    """step(cache, tables {"latent": (B, MBS) i32}, tokens (B,), active
    (B,) bool) -> (cache, logits (B, vocab) f32). ``cache["counters"]``
    is this step's expert-layer counters summed over its routed layers."""
    bs = page.block_size

    def step(params, cache, tables, tokens, active):
        lengths = cache["length"]
        table = tables[KIND]
        B = tokens.shape[0]
        cos, sin = _rope_tables(cfg, page.max_seq)
        with part("embed"):
            x = params["embed"].astype(cfg.dtype)[tokens][:, None, :]
        with part("kv_store"):
            blk = jnp.where(active, table[jnp.arange(B), lengths // bs], 0)
            off = lengths % bs
            # a slot that is not running attends nothing, whatever stale
            # length it keeps
            att_len = jnp.where(active, lengths + 1, 0)
            work = mla.paged_mla_decode_work(      # once for every layer
                att_len, bs, page.max_blocks_per_seq)
        pool = cache[KIND]
        counters = jnp.zeros((len(moe.COUNTERS),), jnp.float32)
        for l, layer in enumerate(params["layers"]):
            q_nope, q_rope, c_kv, k_rope = _latents(
                x, layer, cfg, cos, sin, lengths[:, None])
            with part("kv_store"):
                rows = _rows(c_kv[:, 0], k_rope[:, 0], cfg)
            pool = _store(pool, (l, blk, off), rows)
            out = attend_absorbed(q_nope[:, 0], q_rope[:, 0], pool, l, table,
                                  att_len, layer, cfg, work)
            with part("attn_proj"):
                x = x + jnp.einsum("bhd,hde->be", out,
                                   layer["wo"].astype(x.dtype))[:, None, :]
            with part("mlp"):
                normed = rmsnorm(x[:, 0], layer["mlp_norm"], cfg.norm_eps)
            y, c = _mlp(normed, layer, cfg, active)
            x = x + y[:, None, :]
            counters = counters + c
        new = {KIND: pool, "counters": counters,
               "length": jnp.where(active, lengths + 1, lengths)}
        return new, _head(x[:, 0], params, cfg)

    return _bind_params(jax.jit(step, donate_argnums=(1,)), params)


def make_prefill(params: Params, cfg: AxK1Config, page: PagedConfig):
    """prefill(cache, table_rows {"latent": (MBS,) i32}, tokens (1, P)
    padded, true_len, slot) -> (cache, last_logits (vocab,) f32). P a
    multiple of the block size. Attention runs over the prompt itself in
    the expanded form; its latent rows go to the blocks the table names."""
    bs = page.block_size

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("pad_len",))
    def prefill(params, cache, table_rows, tokens, true_len, slot,
                pad_len: int):
        nblk = pad_len // bs
        cos, sin = _rope_tables(cfg, pad_len)
        with part("embed"):
            x = params["embed"].astype(cfg.dtype)[tokens]      # (1, P, h)
        valid = jnp.arange(pad_len) < true_len
        with part("kv_store"):
            dest = jnp.where(jnp.arange(nblk) * bs < true_len,
                             table_rows[KIND][:nblk], 0)
        pool = cache[KIND]
        counters = jnp.zeros((len(moe.COUNTERS),), jnp.float32)
        for l, layer in enumerate(params["layers"]):
            q_nope, q_rope, c_kv, k_rope = _latents(x, layer, cfg, cos, sin,
                                                    None)
            out = attend_expanded(q_nope, q_rope, c_kv, k_rope, layer, cfg)
            with part("attn_proj"):
                x = x + jnp.einsum("bshd,hde->bse", out,
                                   layer["wo"].astype(x.dtype))
            with part("kv_store"):
                rows = jnp.where(valid[:, None],
                                 _rows(c_kv[0], k_rope[0], cfg), 0.0)
            pool = _store(pool, (l, dest), rows.reshape(nblk, bs, -1))
            with part("mlp"):
                normed = rmsnorm(x[0], layer["mlp_norm"], cfg.norm_eps)
            y, c = _mlp(normed, layer, cfg, valid,
                        "grouped_expert_matmul_prefill")
            x = x + y[None]
            counters = counters + c
        new = {KIND: pool, "counters": counters,
               "length": cache["length"].at[slot].set(true_len)}
        last = x[0, jnp.maximum(true_len - 1, 0)]
        return new, _head(last, params, cfg)

    def call(cache, table_rows, tokens, true_len, slot):
        pad_len = tokens.shape[1]
        if pad_len % bs:
            raise ValueError(f"padded prompt {pad_len} not a multiple of "
                             f"block_size {bs}")
        return prefill(params, cache,
                       {KIND: jnp.asarray(table_rows[KIND], jnp.int32)},
                       tokens, jnp.asarray(true_len, jnp.int32),
                       jnp.asarray(slot, jnp.int32), pad_len=pad_len)

    call.jitted = prefill
    return call


# ------------------------------------------------- what the engine is given
class AxK1Serving:
    """The model as :class:`ray_tpu.serve.llm.LLMEngine` takes it
    (:mod:`ray_tpu.models.serving`)."""

    def __init__(self, config: AxK1Config):
        self.config = config

    def init_params(self, key):
        return init_params(self.config, key)

    def paged(self, params, *, num_slots: int, max_seq: int,
              block_size: int, pool_tokens: int):
        from ray_tpu.models.serving import PagedPrograms

        page = page_of(max_seq=max_seq, block_size=block_size,
                       pool_tokens=pool_tokens)
        return PagedPrograms(
            alloc=make_manager(page, num_slots),
            cache=init_cache(self.config, page, num_slots),
            prefill=make_prefill(params, self.config, page),
            decode=make_decode_step(params, self.config, page),
            page=page, counters=moe.COUNTERS)
