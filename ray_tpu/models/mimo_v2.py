"""A decoder that mixes full and sliding-window attention layers with a
routed expert MLP held by share (the language model of the ``mimo_v2``
family), on the paged serving path.

Layer ``l`` is a full-attention or a window layer by ``layer_kinds[l]``
(0 / 1) and has a dense SwiGLU or a routed MLP by ``moe_layers[l]``. Both
attention kinds: ``n_heads`` query heads, keys ``head_dim`` wide and
values ``v_head_dim`` wide, scale ``head_dim ** -0.5``, rotary on the
first ``rotary_dim`` dimensions only (half-split layout) at the kind's
own theta, values multiplied by ``value_scale`` before the weighted sum.
A window layer has its own number of KV heads, attends keys
``i - window < j <= i`` and adds a learned per-head sink logit to the
softmax's denominator. The routed MLP is
:func:`ray_tpu.models.moe.experts_by_share`: sigmoid scores over all
``n_experts``, ``top_k`` chosen with the correction bias, and the part
of the sum that the ``experts_held`` here give.

Two kinds of KV state, one manager (:class:`KVStateManager`): a pool for
each kind, each with its own row shapes, a block table a slot and kind;
full layers keep the whole sequence, window layers only the blocks the
window still touches (the allocator gives the others back). The layers
are not alike, so the programs unroll them; a pool is still ONE donated
buffer that every layer of its kind updates in place
(``pool.at[l, block, offset].set``).

A pool row is lane-dense: one token's keys (or values) of every KV head.
A key is ``head_dim`` = rotated + unrotated columns wide, which is no
multiple of the 128 lanes at the published 64 + 128, so the row is
packed, not padded (:func:`pack_keys`): the unrotated parts head after
head, then the rotated parts of a PAIR of heads in one chunk. A query is
packed to match (its rotated part in its head's half of the pair's
chunk, zeros in the other), so ``q . k`` is unchanged and every slice
the decode kernel takes is a whole aligned chunk.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import moe
from ray_tpu.models import paged_cache as pc
from ray_tpu.models.decoding import _bind_params
from ray_tpu.models.paged_cache import KVStateManager, PagedConfig
from ray_tpu.ops.attention import prompt_attention
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.pallas import paged_hybrid_decode_attention as pha
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.util.profiling import part

Params = Dict[str, Any]
KINDS = pc.HYBRID_KINDS             # layer_kinds 0, 1


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 256
    hidden: int = 64
    n_layers: int = 3
    n_heads: int = 4
    n_kv_heads: int = 2             # full-attention layers
    swa_n_kv_heads: int = 4         # window layers
    head_dim: int = 24              # q . k width
    v_head_dim: int = 16
    rotary_dim: int = 8             # int(partial_rotary_factor * head_dim)
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    window: int = 16
    value_scale: float = 0.707
    layer_kinds: Tuple[int, ...] = (0, 1, 1)     # 0 full, 1 window
    moe_layers: Tuple[int, ...] = (0, 1, 1)      # 0 dense, 1 routed
    mlp_dim: int = 128              # the dense layers' SwiGLU
    expert_dim: int = 32
    n_experts: int = 16             # the router's width
    top_k: int = 4
    experts_held: Tuple[int, int] = (0, 16)      # (first, count) here
    routed_scale: float = 1.0
    norm_eps: float = 1e-5
    max_seq: int = 2048
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if len(self.layer_kinds) < self.n_layers or \
                len(self.moe_layers) < self.n_layers:
            raise ValueError("layer_kinds / moe_layers shorter than "
                             "n_layers")
        if 2 * self.rotary_dim != self.head_dim - self.rotary_dim:
            raise ValueError(
                "the packed key row needs rotary_dim = a third of "
                f"head_dim, got {self.rotary_dim} of {self.head_dim}")
        if self.n_kv_heads % 2 or self.swa_n_kv_heads % 2:
            raise ValueError("KV heads are packed in pairs")

    def kind(self, l: int) -> str:
        return KINDS[self.layer_kinds[l]]

    def kv_heads(self, kind: str) -> int:
        return self.n_kv_heads if kind == "full" else self.swa_n_kv_heads

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(l for l in range(self.n_layers)
                     if self.kind(l) == kind)

    def serving_model(self):
        return MimoV2Serving(self)


# ------------------------------------------------------------ packed rows
def key_slices(cfg: MimoV2Config, kind: str):
    """For each KV head, the starts of the chunks (each ``head_dim -
    rotary_dim`` wide) of a packed key row that make up its key."""
    KV, c = cfg.kv_heads(kind), cfg.head_dim - cfg.rotary_dim
    return tuple((j * c, KV * c + (j // 2) * c) for j in range(KV))


def pack_keys(k, cfg: MimoV2Config):
    """(..., KV, head_dim) -> (..., KV * head_dim): the unrotated parts
    head after head, then the rotated parts head after head (so a pair
    of heads shares one chunk)."""
    r = cfg.rotary_dim
    lead = k.shape[:-2]
    return jnp.concatenate([k[..., r:].reshape(*lead, -1),
                            k[..., :r].reshape(*lead, -1)], axis=-1)


def pack_queries(q, cfg: MimoV2Config, kind: str):
    """(..., H, head_dim) -> (..., H, 2 * chunk): the unrotated part,
    then the rotated part in the half of a chunk where the head's KV
    head keeps its own (zeros in the other half)."""
    r, H = cfg.rotary_dim, q.shape[-2]
    odd = ((jnp.arange(H) // (H // cfg.kv_heads(kind))) % 2 == 1)[:, None]
    rot, zero = q[..., :r], jnp.zeros_like(q[..., :r])
    return jnp.concatenate([q[..., r:], jnp.where(odd, zero, rot),
                            jnp.where(odd, rot, zero)], axis=-1)


# ----------------------------------------------------------------- weights
def param_shapes(cfg: MimoV2Config) -> Params:
    """The tree the builders take, as shapes: ``layers`` is a LIST (the
    layers are not alike). A norm's stored weight ``w`` scales by
    ``1 + w``; the router and its bias are read in float32."""
    c = cfg
    h, H, D, Dv = c.hidden, c.n_heads, c.head_dim, c.v_head_dim
    G = c.experts_held[1]
    layers = []
    for l in range(c.n_layers):
        KV = c.kv_heads(c.kind(l))
        layer = {"attn_norm": (h,), "wq": (h, H, D), "wk": (h, KV, D),
                 "wv": (h, KV, Dv), "wo": (H, Dv, h), "mlp_norm": (h,)}
        if c.kind(l) == "window":
            layer["sink"] = (H,)
        if c.moe_layers[l]:
            layer.update(router=(h, c.n_experts),
                         router_bias=(c.n_experts,),
                         we_gate=(G, h, c.expert_dim),
                         we_up=(G, h, c.expert_dim),
                         we_down=(G, c.expert_dim, h))
        else:
            layer.update(w_gate=(h, c.mlp_dim), w_up=(h, c.mlp_dim),
                         w_down=(c.mlp_dim, h))
        layers.append(layer)
    return {"embed": (c.vocab_size, h), "layers": layers,
            "final_norm": (h,), "lm_head": (h, c.vocab_size)}


def param_stds(cfg: MimoV2Config):
    """(default standard deviation, {leaf name: its own})."""
    std = cfg.hidden ** -0.5
    out = std / (2 * cfg.n_layers) ** 0.5
    return std, {"attn_norm": 0.1, "mlp_norm": 0.1, "final_norm": 0.1,
                 "wo": out, "w_down": out, "we_down": out,
                 "sink": 1.0, "router_bias": 0.01}


def init_params(cfg: MimoV2Config, key: jax.Array) -> Params:
    from ray_tpu.models.serving import init_from_shapes

    return init_from_shapes(param_shapes(cfg), key, *param_stds(cfg),
                            cfg.dtype)


# ------------------------------------------------------------------- cache
# the assembly is :mod:`ray_tpu.models.paged_cache`'s (every model of
# full and window layers calls it); here is what this model's rows hold
def pages(cfg: MimoV2Config, **geometry) -> Dict[str, PagedConfig]:
    return pc.hybrid_pages(cfg.window, **geometry)


def init_cache(cfg: MimoV2Config, page: Dict[str, PagedConfig],
               num_slots: int):
    return pc.init_hybrid_cache(
        page, num_slots,
        {kind: (len(cfg.layers_of(kind)),
                cfg.kv_heads(kind) * cfg.head_dim,
                cfg.kv_heads(kind) * cfg.v_head_dim) for kind in KINDS},
        len(moe.COUNTERS), cfg.dtype)


def make_manager(cfg: MimoV2Config, page: Dict[str, PagedConfig],
                 num_slots: int) -> KVStateManager:
    return pc.make_hybrid_manager(page, cfg.window, num_slots)


# ------------------------------------------------------------------ blocks
@part("attn_proj")
def _qkv(x, layer, cfg, cos, sin, positions):
    h = rmsnorm(x, layer["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bse,ehd->bshd", h, layer["wq"].astype(h.dtype))
    k = jnp.einsum("bse,ehd->bshd", h, layer["wk"].astype(h.dtype))
    v = jnp.einsum("bse,ehd->bshd", h, layer["wv"].astype(h.dtype))
    v = (v.astype(jnp.float32) * cfg.value_scale).astype(v.dtype)
    return (apply_rope(q, cos, sin, positions),
            apply_rope(k, cos, sin, positions), v)


def _mlp(x, layer, cfg, valid, kernel_name="grouped_expert_matmul"):
    """x (T, h) after the MLP norm -> ((T, h) in x.dtype, counters)."""
    if "router" in layer:
        y, counters = moe.experts_by_share(
            x, layer, experts_held=cfg.experts_held, top_k=cfg.top_k,
            scale=cfg.routed_scale, valid=valid, kernel_name=kernel_name)
        return y.astype(x.dtype), counters
    with part("mlp"):
        g = x @ layer["w_gate"].astype(x.dtype)
        u = x @ layer["w_up"].astype(x.dtype)
        return ((jax.nn.silu(g) * u) @ layer["w_down"].astype(x.dtype),
                jnp.zeros((len(moe.COUNTERS),), jnp.float32))


@part("head")
def _head(x, params, cfg):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)


def _ropes(cfg, length):
    return {"full": rope_frequencies(cfg.rotary_dim, length, cfg.rope_theta),
            "window": rope_frequencies(cfg.rotary_dim, length,
                                       cfg.swa_rope_theta)}


def _window(cfg, kind):
    return cfg.window if kind == "window" else None


def _attend(q, kc, vc, li, tables, att_len, cfg, kind, sink, work):
    """q (B, H, head_dim) -> (B, H, v_head_dim) over the paged pool;
    ``att_len`` 0 for a slot that is not running, ``work`` from
    :func:`ray_tpu.models.paged_cache.hybrid_decode_work` of the same
    lengths (None off the TPU, where the oracle attends)."""
    kw = dict(scale=cfg.head_dim ** -0.5, k_slices=key_slices(cfg, kind),
              dv=cfg.v_head_dim, sink=sink, window=_window(cfg, kind))
    with part("attn_proj"):
        qp = pack_queries(q, cfg, kind)
    return pha.paged_hybrid_decode(qp, kc, vc, li, tables, att_len,
                                   work=work,
                                   name=f"paged_hybrid_decode_{kind}", **kw)


# ---------------------------------------------------------------- programs
def make_decode_step(params: Params, cfg: MimoV2Config,
                     page: Dict[str, PagedConfig]):
    """step(cache, tables {kind: (B, MBS) i32}, tokens (B,), active (B,)
    bool) -> (cache, logits (B, vocab) f32). ``cache["counters"]`` is
    this step's expert-layer counters summed over its routed layers."""
    bs = page["full"].block_size

    def step(params, cache, tables, tokens, active):
        lengths = cache["length"]
        B = tokens.shape[0]
        ropes = _ropes(cfg, page["full"].max_seq)
        with part("embed"):
            x = params["embed"].astype(cfg.dtype)[tokens][:, None, :]
        blk, off, att_len = pc.hybrid_decode_rows(tables, lengths, active,
                                                  bs)
        work = pc.hybrid_decode_work(att_len, page, cfg.window)
        pools = pc.hybrid_pools(cache)
        index = dict.fromkeys(KINDS, 0)
        counters = jnp.zeros((len(moe.COUNTERS),), jnp.float32)
        for l, layer in enumerate(params["layers"]):
            kind = cfg.kind(l)
            li, index[kind] = index[kind], index[kind] + 1
            q, k, v = _qkv(x, layer, cfg, *ropes[kind], lengths[:, None])
            with part("kv_store"):
                k_rows = pack_keys(k[:, 0], cfg)
            kc, vc = pools[kind] = pc.store_kv_rows(
                pools[kind], (li, blk[kind], off), k_rows,
                v[:, 0].reshape(B, -1))
            out = _attend(q[:, 0], kc, vc, li, tables[kind], att_len,
                          cfg, kind, layer.get("sink"), work[kind])
            with part("attn_proj"):
                x = x + jnp.einsum("bhd,hde->be", out,
                                   layer["wo"].astype(x.dtype))[:, None, :]
            with part("mlp"):
                normed = rmsnorm(x[:, 0], layer["mlp_norm"], cfg.norm_eps)
            y, c = _mlp(normed, layer, cfg, active)
            x = x + y[:, None, :]
            counters = counters + c
        new = pc.hybrid_cache(
            pools, jnp.where(active, lengths + 1, lengths), counters)
        return new, _head(x[:, 0], params, cfg)

    return _bind_params(jax.jit(step, donate_argnums=(1,)), params)


def make_prefill(params: Params, cfg: MimoV2Config,
                 page: Dict[str, PagedConfig]):
    """prefill(cache, table_rows {kind: (MBS,) i32}, tokens (1, P)
    padded, true_len, slot) -> (cache, last_logits (vocab,) f32). P a
    multiple of the block size. Attention runs over the prompt itself
    (full layers causal, window layers in bands); its keys and values go
    to the blocks each kind's table names, those of a window layer that
    the table no longer holds (behind the window) to the null block."""
    bs = page["full"].block_size

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("pad_len",))
    def prefill(params, cache, table_rows, tokens, true_len, slot,
                pad_len: int):
        nblk = pad_len // bs
        ropes = _ropes(cfg, pad_len)
        with part("embed"):
            x = params["embed"].astype(cfg.dtype)[tokens]      # (1, P, h)
        valid = jnp.arange(pad_len) < true_len
        dest = pc.hybrid_prefill_blocks(table_rows, true_len, nblk, bs)
        pools = pc.hybrid_pools(cache)
        index = dict.fromkeys(KINDS, 0)
        counters = jnp.zeros((len(moe.COUNTERS),), jnp.float32)
        for l, layer in enumerate(params["layers"]):
            kind = cfg.kind(l)
            li, index[kind] = index[kind], index[kind] + 1
            q, k, v = _qkv(x, layer, cfg, *ropes[kind], None)
            out = prompt_attention(
                q, k, v, scale=cfg.head_dim ** -0.5, sink=layer.get("sink"),
                window=_window(cfg, kind))
            with part("attn_proj"):
                x = x + jnp.einsum("bshd,hde->bse", out,
                                   layer["wo"].astype(x.dtype))
            with part("kv_store"):
                kb = jnp.where(valid[:, None], pack_keys(k[0], cfg), 0.0)
                vb = jnp.where(valid[:, None], v[0].reshape(pad_len, -1),
                               0.0)
            pools[kind] = pc.store_kv_rows(pools[kind], (li, dest[kind]),
                                           kb.reshape(nblk, bs, -1),
                                           vb.reshape(nblk, bs, -1))
            with part("mlp"):
                normed = rmsnorm(x[0], layer["mlp_norm"], cfg.norm_eps)
            y, c = _mlp(normed, layer, cfg, valid,
                        "grouped_expert_matmul_prefill")
            x = x + y[None]
            counters = counters + c
        new = pc.hybrid_cache(
            pools, cache["length"].at[slot].set(true_len), counters)
        last = x[0, jnp.maximum(true_len - 1, 0)]
        return new, _head(last, params, cfg)

    return pc.bind_hybrid_prefill(prefill, params, bs)


# ------------------------------------------------- what the engine is given
class MimoV2Serving:
    """The model as :class:`ray_tpu.serve.llm.LLMEngine` takes it
    (:mod:`ray_tpu.models.serving`)."""

    def __init__(self, config: MimoV2Config):
        self.config = config

    def init_params(self, key):
        return init_params(self.config, key)

    def paged(self, params, *, num_slots: int, max_seq: int,
              block_size: int, pool_tokens: int):
        from ray_tpu.models.serving import PagedPrograms

        page = pages(self.config, num_slots=num_slots, max_seq=max_seq,
                     block_size=block_size, pool_tokens=pool_tokens)
        return PagedPrograms(
            alloc=make_manager(self.config, page, num_slots),
            cache=init_cache(self.config, page, num_slots),
            prefill=make_prefill(params, self.config, page),
            decode=make_decode_step(params, self.config, page),
            page=page["full"], counters=moe.COUNTERS)
