"""A decoder that mixes full and sliding-window attention layers whose
QUERY heads differ by layer kind over one count of KV heads, gates each
head's attention output, and has a shared expert beside a softmax-routed
expert MLP (the language model of the ``laguna`` family), on the paged
serving path.

Layer ``l`` is a full-attention or a window layer by ``layer_kinds[l]``
(0 / 1) and has a dense SwiGLU or a routed MLP by ``moe_layers[l]``.
Attention, with ``H_l = heads[l]`` query heads, ``n_kv_heads`` KV heads
and ``head_dim`` wide keys and values in both kinds::

    q, k, v = x W_q, x W_k, x W_v          H_l x D, KV x D, KV x D
    q, k    = rope_kind(q), rope_kind(k)
    o_h     = softmax(q_h . k_g(h) / sqrt(D)) v_g(h)    g(h) = h // (H_l / KV)
    o_h    <- sigmoid(x W_g)_h o_h          the gate: W_g (hidden, H_l)
    out     = o W_o                          from H_l x D

with ``x`` the normed input throughout, causal, a window layer's query i
seeing keys ``i - window < j <= i``. Rope by kind: a window layer rotates
``swa_rotary_dim`` dimensions at ``swa_rope_theta`` unscaled; a full
layer the first ``rotary_dim`` at ``rope_theta`` under YaRN (``yarn``: a
published ``attention_factor`` is the TABLES' factor, ``mscale_all_dim``
0, so a rotated ``q . k`` carries its square and the unrotated
dimensions do not).

The MLP of a routed layer is ``shared(x) + routed_scale * sum over the
top_k of w_e E_e(x)``: :func:`ray_tpu.models.moe.shared_expert` and
:func:`ray_tpu.models.moe.experts_by_share` with ``score="softmax"``
(``p = softmax(x_f32 W_r)``, the ``top_k`` largest, ``w = p / sum over
the chosen``). ``experts_held`` may be the whole router's width: one
chip then holds every expert and the layer's result is whole.

The two kinds of KV state, their pools, the manager and the decode
kernel's work lists are :mod:`ray_tpu.models.paged_cache`'s (shared with
``mimo_v2.py``); a pool row is one token's keys (or values) of every KV
head, head after head, which is lane-dense as it stands at a head of
128, so nothing is packed. Decode attends through
:mod:`ray_tpu.ops.pallas.paged_hybrid_decode_attention`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import moe
from ray_tpu.models import paged_cache as pc
from ray_tpu.models.decoding import _bind_params
from ray_tpu.models.paged_cache import KVStateManager, PagedConfig
from ray_tpu.ops.attention import prompt_attention
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.pallas import paged_hybrid_decode_attention as pha
from ray_tpu.ops.rope import YarnScaling, apply_rope, rope_frequencies
from ray_tpu.util.profiling import part

Params = Dict[str, Any]
KINDS = pc.HYBRID_KINDS             # layer_kinds 0, 1


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 256
    hidden: int = 64
    n_layers: int = 5
    heads: Tuple[int, ...] = (6, 8, 8, 8, 6)     # query heads of layer l
    n_kv_heads: int = 2
    head_dim: int = 16
    rotary_dim: int = 8             # full layers: half a head
    swa_rotary_dim: int = 16        # window layers: the whole head
    rope_theta: float = 5e5
    swa_rope_theta: float = 1e4
    yarn: Optional[YarnScaling] = YarnScaling(   # full layers only
        factor=4.0, original_max_seq=32, beta_fast=4.0, beta_slow=1.0,
        mscale=1.0, mscale_all_dim=0.0)
    window: int = 24
    layer_kinds: Tuple[int, ...] = (0, 1, 1, 1, 0)   # 0 full, 1 window
    moe_layers: Tuple[int, ...] = (0, 1, 1, 1, 1)    # 0 dense, 1 routed
    mlp_dim: int = 128              # the dense layers' SwiGLU
    expert_dim: int = 16            # narrower than hidden
    shared_dim: int = 16
    n_experts: int = 16             # the router's width
    top_k: int = 4
    experts_held: Tuple[int, int] = (0, 16)      # (first, count) here
    routed_scale: float = 2.5
    norm_eps: float = 1e-6
    max_seq: int = 2048
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        n = self.n_layers
        if min(len(self.heads), len(self.layer_kinds),
               len(self.moe_layers)) < n:
            raise ValueError("heads / layer_kinds / moe_layers shorter "
                             "than n_layers")
        if any(H % self.n_kv_heads for H in self.heads[:n]):
            raise ValueError(f"query heads {self.heads[:n]} not multiples "
                             f"of the {self.n_kv_heads} KV heads")

    def kind(self, l: int) -> str:
        return KINDS[self.layer_kinds[l]]

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(l for l in range(self.n_layers)
                     if self.kind(l) == kind)

    def window_of(self, kind: str) -> Optional[int]:
        return self.window if kind == "window" else None

    def scale(self, kind: str) -> float:
        """The softmax scale: YaRN's own factor on it is 1 where the
        published factor is the tables' (``mscale_all_dim`` 0)."""
        s = self.head_dim ** -0.5
        return s * self.yarn.attention_factor \
            if kind == "full" and self.yarn else s

    def serving_model(self):
        return LagunaServing(self)


# ----------------------------------------------------------------- weights
def param_shapes(cfg: LagunaConfig) -> Params:
    """The tree the builders take, as shapes: ``layers`` is a LIST (the
    layers are not alike). A norm's stored weight ``w`` scales by
    ``1 + w``; the router is read in float32."""
    c = cfg
    h, D, KV, G = c.hidden, c.head_dim, c.n_kv_heads, c.experts_held[1]
    layers = []
    for l in range(c.n_layers):
        H = c.heads[l]
        layer = {"attn_norm": (h,), "wq": (h, H, D), "wk": (h, KV, D),
                 "wv": (h, KV, D), "w_out_gate": (h, H), "wo": (H, D, h),
                 "mlp_norm": (h,)}
        if c.moe_layers[l]:
            layer.update(router=(h, c.n_experts),
                         ws_gate=(h, c.shared_dim), ws_up=(h, c.shared_dim),
                         ws_down=(c.shared_dim, h),
                         we_gate=(G, h, c.expert_dim),
                         we_up=(G, h, c.expert_dim),
                         we_down=(G, c.expert_dim, h))
        else:
            layer.update(w_gate=(h, c.mlp_dim), w_up=(h, c.mlp_dim),
                         w_down=(c.mlp_dim, h))
        layers.append(layer)
    return {"embed": (c.vocab_size, h), "layers": layers,
            "final_norm": (h,), "lm_head": (h, c.vocab_size)}


def param_stds(cfg: LagunaConfig):
    """(default standard deviation, {leaf name: its own}): a matrix at
    its fan-in ** -0.5, every projection back into the residual stream
    scaled down by ``sqrt(2 L)`` (a routed expert's by ``routed_scale``
    too, which multiplies the routed sum), norm weights at 0.1."""
    std = cfg.hidden ** -0.5
    out = std / (2 * cfg.n_layers) ** 0.5
    return std, {"attn_norm": 0.1, "mlp_norm": 0.1, "final_norm": 0.1,
                 "wo": out, "w_down": out, "ws_down": out,
                 "we_down": out / cfg.routed_scale}


def init_params(cfg: LagunaConfig, key: jax.Array) -> Params:
    from ray_tpu.models.serving import init_from_shapes

    return init_from_shapes(param_shapes(cfg), key, *param_stds(cfg),
                            cfg.dtype)


# ------------------------------------------------------------------- cache
# the assembly is :mod:`ray_tpu.models.paged_cache`'s; a row here is
# every KV head's key (or value), head after head, in both kinds
def key_slices(cfg: LagunaConfig):
    """For each KV head, the start of the one ``head_dim`` wide chunk of
    a key row that is its key."""
    return tuple((j * cfg.head_dim,) for j in range(cfg.n_kv_heads))


def pages(cfg: LagunaConfig, **geometry) -> Dict[str, PagedConfig]:
    return pc.hybrid_pages(cfg.window, **geometry)


def init_cache(cfg: LagunaConfig, page: Dict[str, PagedConfig],
               num_slots: int):
    width = cfg.n_kv_heads * cfg.head_dim
    return pc.init_hybrid_cache(
        page, num_slots,
        {kind: (len(cfg.layers_of(kind)), width, width) for kind in KINDS},
        len(moe.COUNTERS), cfg.dtype)


def make_manager(cfg: LagunaConfig, page: Dict[str, PagedConfig],
                 num_slots: int) -> KVStateManager:
    return pc.make_hybrid_manager(page, cfg.window, num_slots)


# ------------------------------------------------------------------ blocks
def _ropes(cfg, length):
    return {"full": rope_frequencies(cfg.rotary_dim, length, cfg.rope_theta,
                                     yarn=cfg.yarn),
            "window": rope_frequencies(cfg.swa_rotary_dim, length,
                                       cfg.swa_rope_theta)}


@part("attn_proj")
def _qkv(x, layer, cfg, cos, sin, positions):
    """x (B, S, h) -> the normed input, q (B, S, H_l, D), k, v
    (B, S, KV, D), q and k rotated."""
    h = rmsnorm(x, layer["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bse,ehd->bshd", h, layer["wq"].astype(h.dtype))
    k = jnp.einsum("bse,ehd->bshd", h, layer["wk"].astype(h.dtype))
    v = jnp.einsum("bse,ehd->bshd", h, layer["wv"].astype(h.dtype))
    return (h, apply_rope(q, cos, sin, positions),
            apply_rope(k, cos, sin, positions), v)


@part("attn_gate")
def gate_heads(out, h, layer):
    """``o_h <- sigmoid(h W_g)_h o_h``: out (..., H, D) and the normed
    input h (..., hidden) it was attended from; the sigmoid in float32."""
    g = jax.nn.sigmoid(jnp.einsum(
        "...e,eh->...h", h, layer["w_out_gate"].astype(h.dtype),
        preferred_element_type=jnp.float32))
    return (out.astype(jnp.float32) * g[..., None]).astype(out.dtype)


def _mlp(x, layer, cfg, valid, kernel_name="grouped_expert_matmul"):
    """x (T, h) after the MLP norm -> ((T, h) in x.dtype, counters)."""
    if "router" in layer:
        y, counters = moe.experts_by_share(
            x, layer, experts_held=cfg.experts_held, top_k=cfg.top_k,
            scale=cfg.routed_scale, valid=valid, kernel_name=kernel_name,
            score="softmax")
        return (moe.shared_expert(x, layer) + y).astype(x.dtype), counters
    with part("mlp"):
        return (moe.swiglu(x, layer["w_gate"], layer["w_up"],
                           layer["w_down"]),
                jnp.zeros((len(moe.COUNTERS),), jnp.float32))


@part("head")
def _head(x, params, cfg):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)


def _attend(q, kc, vc, li, tables, att_len, cfg, kind, work):
    """q (B, H_l, D) -> (B, H_l, D) over the paged pool; ``att_len`` 0
    for a slot that is not running, ``work`` from
    :func:`ray_tpu.models.paged_cache.hybrid_decode_work` of the same
    lengths (None off the TPU, where the oracle attends)."""
    kw = dict(scale=cfg.scale(kind), k_slices=key_slices(cfg),
              dv=cfg.head_dim, window=cfg.window_of(kind))
    return pha.paged_hybrid_decode(q, kc, vc, li, tables, att_len,
                                   work=work,
                                   name=f"paged_hybrid_decode_{kind}", **kw)


# ---------------------------------------------------------------- programs
def make_decode_step(params: Params, cfg: LagunaConfig,
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
            h, q, k, v = _qkv(x, layer, cfg, *ropes[kind], lengths[:, None])
            kc, vc = pools[kind] = pc.store_kv_rows(
                pools[kind], (li, blk[kind], off),
                k[:, 0].reshape(B, -1), v[:, 0].reshape(B, -1))
            out = _attend(q[:, 0], kc, vc, li, tables[kind], att_len, cfg,
                          kind, work[kind])
            out = gate_heads(out, h[:, 0], layer)
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


def make_prefill(params: Params, cfg: LagunaConfig,
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
            h, q, k, v = _qkv(x, layer, cfg, *ropes[kind], None)
            out = prompt_attention(
                q, k, v, scale=cfg.scale(kind), window=cfg.window_of(kind))
            out = gate_heads(out, h, layer)
            with part("attn_proj"):
                x = x + jnp.einsum("bshd,hde->bse", out,
                                   layer["wo"].astype(x.dtype))
            with part("kv_store"):
                kb = jnp.where(valid[:, None], k[0].reshape(pad_len, -1),
                               0.0)
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
class LagunaServing:
    """The model as :class:`ray_tpu.serve.llm.LLMEngine` takes it
    (:mod:`ray_tpu.models.serving`)."""

    def __init__(self, config: LagunaConfig):
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
