"""A routed decoder that generates by DIFFUSION OVER BLOCKS (the language
model of the ``sdar_moe`` family), on the paged serving path.

The body is the Qwen3-MoE layer: pre-norm RMSNorm, grouped-query
attention with ONE RMSNorm over the head's width on every query and key
head before rotary (``q_norm`` / ``k_norm``, a ``head_dim`` vector each,
shared by the heads), rotary on the whole head, and in every layer a
softmax-routed SwiGLU expert MLP with no shared expert
(:func:`ray_tpu.models.moe.experts_by_share` with ``score="softmax"``:
``p = softmax(x_f32 W_r)``, the ``top_k`` largest, ``w = p / sum over
the chosen``), the expert set held whole or by share. Untied head.

What the family changes is the mask and the generation loop. With ``B =
block_length``, position ``t`` sees position ``s`` iff ``s // B <= t //
B``: causal between blocks, everything inside its own block. The logit
row at position ``t`` scores the token AT ``t`` (no shift). A sequence
grows a block at a time: the block's ``B`` positions start undecided and
are fed ``mask_token_id``; a DENOISING step runs all ``B`` through the
model against the cached prefix, takes at each undecided position the
token picked from its logits and that token's softmax probability as its
confidence, and decides the most confident few (:func:`decide_static`);
a block with no undecided position runs ONE more step on its decided
tokens, whose K/V rows are the ones that stay (the COMMIT), and the next
block begins.

Three programs, the engine's to run (:mod:`ray_tpu.models.serving`):

- the prefill of a prompt's whole blocks under the block-causal mask
  (:func:`ray_tpu.ops.attention.prompt_attention` with ``span = B``);
- the block step (:func:`make_block_step`), one for all slots: it writes
  the block's provisional K/V rows at ``length .. length + B - 1`` and
  attends over ``length + B`` rows. Inside a block every query sees the
  same keys, so once the rows are stored the attention is the paged
  decode kernel with ``B x group`` query rows a KV head
  (:mod:`ray_tpu.ops.pallas.paged_decode_attention`, ``lengths = length
  + B``). A slot whose positions are all decided commits: its length
  grows by ``B``;
- the deciding (:func:`make_decide`), on the step's logits, all on the
  device.

The cache is the dense paged one (``k`` / ``v`` pools of (layers, blocks,
block size, KV * D), a :class:`BlockAllocator`), carried whole and
updated in place; ``cache["counters"]`` is a program's expert-layer
counters summed over its layers.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import moe
from ray_tpu.models.decoding import _bind_padded, _bind_params
from ray_tpu.models.paged_cache import (BlockAllocator, PagedConfig,
                                        fold_heads, store_kv_rows)
from ray_tpu.ops.attention import prompt_attention
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.pallas.paged_decode_attention import (paged_decode,
                                                       paged_decode_work)
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.util.profiling import part

Params = Dict[str, Any]
RULES = ("low_confidence_static",)


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    vocab_size: int = 256
    hidden: int = 64
    n_layers: int = 4
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 1e6
    expert_dim: int = 32
    n_experts: int = 8              # the router's width
    top_k: int = 2
    experts_held: Tuple[int, int] = (0, 8)       # (first, count) here
    norm_eps: float = 1e-6
    max_seq: int = 2048
    dtype: Any = jnp.bfloat16
    # generation: blocks of ``block_length`` positions, each denoised in
    # ``denoising_steps`` steps by ``remasking``'s rule and then committed
    block_length: int = 4
    denoising_steps: int = 2
    mask_token_id: int = 255
    remasking: str = "low_confidence_static"

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"query heads {self.n_heads} not a multiple "
                             f"of the {self.n_kv_heads} KV heads")
        B = self.block_length
        if B < 1 or B & (B - 1):
            raise ValueError(f"block_length {B}: a power of two")
        if not 1 <= self.denoising_steps <= B:
            raise ValueError(f"denoising_steps {self.denoising_steps}: "
                             f"1 .. block_length {B}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} outside "
                             f"the vocabulary of {self.vocab_size}")
        if self.remasking not in RULES:
            raise ValueError(f"remasking {self.remasking!r}: one of "
                             f"{RULES}")

    def step_quota(self, undecided: int) -> int:
        """Positions ONE denoising step decides of a block that began
        with ``undecided`` of them (``low_confidence_static``: the same
        number every step, so that ``denoising_steps`` steps leave
        none). The engine counts by this what a step will do to a slot;
        :func:`decide_static` ranks the positions."""
        return -(-undecided // self.denoising_steps)

    def serving_model(self):
        return SdarServing(self)


# ----------------------------------------------------------------- weights
def param_shapes(cfg: SdarConfig) -> Params:
    """The tree the builders take, as shapes: ``layers`` a LIST. A
    norm's stored weight ``w`` scales by ``1 + w`` (the head norms
    ``q_norm`` / ``k_norm`` too); the router is read in float32."""
    c = cfg
    h, H, KV, D = c.hidden, c.n_heads, c.n_kv_heads, c.head_dim
    G, m = c.experts_held[1], c.expert_dim
    layer = {"attn_norm": (h,), "wq": (h, H, D), "wk": (h, KV, D),
             "wv": (h, KV, D), "q_norm": (D,), "k_norm": (D,),
             "wo": (H, D, h), "mlp_norm": (h,), "router": (h, c.n_experts),
             "we_gate": (G, h, m), "we_up": (G, h, m), "we_down": (G, m, h)}
    return {"embed": (c.vocab_size, h),
            "layers": [dict(layer) for _ in range(c.n_layers)],
            "final_norm": (h,), "lm_head": (h, c.vocab_size)}


def param_stds(cfg: SdarConfig):
    """(default standard deviation, {leaf name: its own})."""
    std = cfg.hidden ** -0.5
    out = std / (2 * cfg.n_layers) ** 0.5
    return std, {"attn_norm": 0.1, "mlp_norm": 0.1, "final_norm": 0.1,
                 "q_norm": 0.1, "k_norm": 0.1, "wo": out, "we_down": out}


def init_params(cfg: SdarConfig, key: jax.Array) -> Params:
    from ray_tpu.models.serving import init_from_shapes

    return init_from_shapes(param_shapes(cfg), key, *param_stds(cfg),
                            cfg.dtype)


# ------------------------------------------------------------------- cache
def init_cache(cfg: SdarConfig, page: PagedConfig, num_slots: int):
    shape = (cfg.n_layers, page.num_blocks, page.block_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "length": jnp.zeros((num_slots,), jnp.int32),
            "counters": jnp.zeros((len(moe.COUNTERS),), jnp.float32)}


def make_page(cfg: SdarConfig, *, max_seq: int, block_size: int,
              pool_tokens: int) -> PagedConfig:
    if block_size % cfg.block_length or max_seq % cfg.block_length:
        raise ValueError(
            f"kv_block_size={block_size} and max_seq={max_seq} must be "
            f"multiples of block_length={cfg.block_length}: a block's rows "
            "lie in one block of the pool and end inside the sequence")
    return PagedConfig(num_blocks=1 + -(-pool_tokens // block_size),
                       block_size=block_size, max_seq=max_seq)  # + null


# ------------------------------------------------------------------ blocks
def _qkv(x, layer, cfg, cos, sin, positions):
    """x (B, S, h) -> q (B, S, H, D), k, v (B, S, KV, D): q and k normed
    over the head's width, then rotated."""
    with part("attn_proj"):
        h = rmsnorm(x, layer["attn_norm"], cfg.norm_eps)
        q = jnp.einsum("bse,ehd->bshd", h, layer["wq"].astype(h.dtype))
        k = jnp.einsum("bse,ehd->bshd", h, layer["wk"].astype(h.dtype))
        v = jnp.einsum("bse,ehd->bshd", h, layer["wv"].astype(h.dtype))
    with part("qk_norm"):
        q = rmsnorm(q, layer["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, layer["k_norm"], cfg.norm_eps)
    with part("attn_proj"):
        return (apply_rope(q, cos, sin, positions),
                apply_rope(k, cos, sin, positions), v)


def _mlp(x, layer, cfg, valid, kernel_name):
    """x (T, h) after the MLP norm -> ((T, h) in x.dtype, counters)."""
    y, counters = moe.experts_by_share(
        x, layer, experts_held=cfg.experts_held, top_k=cfg.top_k,
        valid=valid, kernel_name=kernel_name, score="softmax")
    return y.astype(x.dtype), counters


@part("head")
def _head(x, params, cfg):
    """(..., h) -> (..., vocab) float32. One row (a prefill's) is
    multiplied in float32 operands, as the other models' heads are. The
    block step's ``slots x block_length`` rows keep the operands in the
    model's dtype and accumulate in float32: against the whole
    vocabulary the MXU's rate for float32 operands is a third of that."""
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if x.ndim == 1:
        return x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    return jnp.dot(x, params["lm_head"].astype(x.dtype),
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- programs
def make_prefill(params: Params, cfg: SdarConfig, page: PagedConfig):
    """prefill(cache, table_row (MBS,) i32, tokens (1, P) padded,
    true_len, slot) -> (cache, logits (vocab,) f32 of row ``true_len -
    1``). ``true_len`` is a whole number of blocks of ``block_length``
    (0: the slot's length is set and nothing else); P a multiple of the
    pool's block size. Block-causal attention over the prompt itself;
    its K/V fill the blocks the table names, padding the null block."""
    bs, span = page.block_size, cfg.block_length

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("pad_len",))
    def prefill(params, cache, table_row, tokens, true_len, slot,
                pad_len: int):
        cos, sin = rope_frequencies(cfg.head_dim, pad_len, cfg.rope_theta)
        with part("embed"):
            x = params["embed"].astype(cfg.dtype)[tokens]      # (1, P, h)
        row = jnp.arange(pad_len)
        valid = row < true_len
        with part("kv_store"):
            # row by row, as the block step writes: while the pool's
            # rows were (KV, D), a write of whole (block size, KV, D)
            # blocks made the chip's compiler lay the WHOLE pool out
            # anew around it where KV heads are fewer than a tile's
            # sublanes (four pool-sized copies a prefill)
            blk = jnp.where(valid, table_row[row // bs], 0)
            off = row % bs
        pool = (cache["k"], cache["v"])
        counters = jnp.zeros((len(moe.COUNTERS),), jnp.float32)
        for l, layer in enumerate(params["layers"]):
            q, k, v = _qkv(x, layer, cfg, cos, sin, None)
            out = prompt_attention(q, k, v, scale=cfg.head_dim ** -0.5,
                                   span=span)
            with part("attn_proj"):
                x = x + jnp.einsum("bshd,hde->bse", out,
                                   layer["wo"].astype(x.dtype))
            pool = store_kv_rows(pool, (l, blk, off), fold_heads(k[0]),
                                 fold_heads(v[0]))
            with part("mlp"):
                normed = rmsnorm(x[0], layer["mlp_norm"], cfg.norm_eps)
            y, c = _mlp(normed, layer, cfg, valid,
                        "grouped_expert_matmul_prefill")
            x = x + y[None]
            counters = counters + c
        new = {"k": pool[0], "v": pool[1],
               "length": cache["length"].at[slot].set(true_len),
               "counters": counters}
        return new, _head(x[0, jnp.maximum(true_len - 1, 0)], params, cfg)

    return _bind_padded(prefill, params, tokens_at=1, multiple_of=bs)


def make_block_step(params: Params, cfg: SdarConfig, page: PagedConfig):
    """step(cache, tables (S, MBS) i32, ids (S, B) i32, decided (S, B)
    bool, active (S,) bool) -> (cache, logits (S, B, vocab) f32): every
    active slot's block of ``B = block_length`` positions through the
    model at once. A decided position is fed its id, any other
    ``mask_token_id`` whatever its id is. The block's K/V rows are
    written at ``length .. length + B - 1`` (the table must cover them)
    and every query attends over ``length + B`` rows. A slot whose
    positions are ALL decided commits: its length grows by ``B`` and the
    rows stay; any other slot's rows are provisional and the next step
    writes them again. An inactive slot writes to the null block and
    attends nothing."""
    bs, Bl = page.block_size, cfg.block_length
    KV, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads

    def block_step(params, cache, tables, ids, decided, active):
        lengths = cache["length"]
        S = ids.shape[0]
        cos, sin = rope_frequencies(
            cfg.head_dim, page.max_blocks_per_seq * bs, cfg.rope_theta)
        with part("embed"):
            feed = jnp.where(decided, ids, cfg.mask_token_id)
            x = params["embed"].astype(cfg.dtype)[feed]        # (S, B, h)
        positions = lengths[:, None] + jnp.arange(Bl)
        with part("kv_store"):
            # a length is a multiple of B and B divides the pool's block:
            # the block's rows lie in ONE block of the pool
            blk = jnp.where(active, tables[jnp.arange(S), lengths // bs],
                            0)[:, None]
            off = (lengths % bs)[:, None] + jnp.arange(Bl)
            att_len = jnp.where(active, lengths + Bl, 0)
        work = paged_decode_work(att_len, bs, page.max_blocks_per_seq)
        valid = jnp.repeat(active, Bl)
        pool = (cache["k"], cache["v"])
        counters = jnp.zeros((len(moe.COUNTERS),), jnp.float32)
        for l, layer in enumerate(params["layers"]):
            q, k, v = _qkv(x, layer, cfg, cos, sin, positions)
            kc, vc = pool = store_kv_rows(pool, (l, blk, off),
                                          fold_heads(k), fold_heads(v))
            with part("attn_proj"):
                # the queries of one KV head together, position after
                # position: the kernel's (B x group) rows a KV head
                qk = q.reshape(S, Bl, KV, g, -1).transpose(0, 2, 1, 3, 4)
                qk = qk.reshape(S, 1, KV * Bl * g, -1)
            out = paged_decode(qk, kc, vc, l, tables, att_len,
                               scale=cfg.head_dim ** -0.5, work=work)
            with part("attn_proj"):
                out = out.reshape(S, KV, Bl, g, -1).transpose(0, 2, 1, 3, 4)
                x = x + jnp.einsum("sbhd,hde->sbe",
                                   out.reshape(S, Bl, KV * g, -1),
                                   layer["wo"].astype(x.dtype))
            with part("mlp"):
                normed = rmsnorm(x.reshape(S * Bl, -1), layer["mlp_norm"],
                                 cfg.norm_eps)
            y, c = _mlp(normed, layer, cfg, valid, "grouped_expert_matmul")
            x = x + y.reshape(S, Bl, -1)
            counters = counters + c
        with part("kv_store"):
            commits = active & jnp.all(decided, axis=1)
            length = jnp.where(commits, lengths + Bl, lengths)
        new = {"k": pool[0], "v": pool[1], "length": length,
               "counters": counters}
        return new, _head(x, params, cfg)

    return _bind_params(jax.jit(block_step, donate_argnums=(1,)), params)


@part("block_decide")
def confidence(logits, temperature=None, key=None, request=None,
               position=None):
    """The token picked at each row of ``logits`` (..., vocab) and the
    softmax probability of that token, both from float32 logits: the
    argmax, or where a row's ``temperature`` is > 0 one exact draw from
    ``softmax(logits / T)`` keyed by (``key``, the row's ``request``
    number, its ``position`` in its sequence), as
    :func:`ray_tpu.serve.llm.sample_ids` keys a token's; the confidence
    is the picked token's probability at temperature 1 either way."""
    logits = logits.astype(jnp.float32)
    picked = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if temperature is not None:
        def draw(row, t, r, p):
            stream = jax.random.fold_in(jax.random.fold_in(key, r), p)
            return jax.random.categorical(stream, row / jnp.maximum(t, 1e-5))

        lead = logits.shape[:-1]
        drawn = jax.vmap(draw)(
            logits.reshape(-1, logits.shape[-1]), temperature.reshape(-1),
            request.reshape(-1), position.reshape(-1)).reshape(lead)
        picked = jnp.where(temperature > 0.0, drawn, picked).astype(
            jnp.int32)
    top = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, picked[..., None], axis=-1)[..., 0]
    conf = jnp.exp(at - top) / jnp.sum(jnp.exp(logits - top[..., None]),
                                       axis=-1)
    return picked, conf


@part("block_decide")
def decide_static(picked, conf, ids, decided, quota):
    """``low_confidence_static``: of each row's undecided positions the
    ``quota`` (S,) most confident become decided with the token picked
    there; equal confidences go to the lower position. ids, decided,
    picked, conf (S, B) -> (ids, decided)."""
    conf = jnp.where(decided, -1.0, conf)
    a, b = conf[:, :, None], conf[:, None, :]
    pos = jnp.arange(conf.shape[1])
    ahead = (b > a) | ((b == a) & (pos[None, None, :] < pos[None, :, None]))
    rank = jnp.sum(ahead & ~decided[:, None, :], axis=2)
    now = ~decided & (rank < quota[:, None])
    return jnp.where(now, picked, ids), decided | now


def make_decide(cfg: SdarConfig):
    """decide(logits (S, B, vocab), ids (S, B), decided (S, B), quota
    (S,) i32, draw=None) -> (ids, decided, out (S, B)), all on the
    device. A slot whose positions are all decided has committed in the
    step these logits are of: ``out`` holds its block's ids, and its
    next block begins (nothing decided). Any other slot decides its
    ``quota`` most confident undecided positions (0: none, a slot that
    did not run). ``draw`` = (temperature (S,), key, request (S,),
    length (S,) before the step) where a slot draws at a temperature;
    None: every pick is the argmax, and no draw is compiled."""
    Bl = cfg.block_length

    def block_decide(logits, ids, decided, quota, draw=None):
        committed = jnp.all(decided, axis=1, keepdims=True)
        if draw is None:
            picked, conf = confidence(logits)
        else:
            temperature, key, request, length = draw
            temperature, request = (jnp.broadcast_to(a[:, None], ids.shape)
                                    for a in (temperature, request))
            picked, conf = confidence(logits, temperature, key, request,
                                      length[:, None] + jnp.arange(Bl))
        new_ids, new_decided = decide_static(picked, conf, ids, decided,
                                             quota)
        with part("block_decide"):
            return (new_ids, new_decided & ~committed,
                    jnp.where(committed, ids, 0))

    return jax.jit(block_decide)


# ------------------------------------------------- what the engine is given
class SdarServing:
    """The model as :class:`ray_tpu.serve.llm.LLMEngine` takes it
    (:mod:`ray_tpu.models.serving`): the paged cache and the prefill,
    no decode step, and the block step in its place."""

    def __init__(self, config: SdarConfig):
        self.config = config

    def init_params(self, key):
        return init_params(self.config, key)

    def paged(self, params, *, num_slots: int, max_seq: int,
              block_size: int, pool_tokens: int):
        from ray_tpu.models.serving import PagedPrograms

        page = make_page(self.config, max_seq=max_seq,
                         block_size=block_size, pool_tokens=pool_tokens)
        return PagedPrograms(
            alloc=BlockAllocator(page, num_slots),
            cache=init_cache(self.config, page, num_slots),
            prefill=make_prefill(params, self.config, page), decode=None,
            page=page, counters=moe.COUNTERS)

    def block_denoise(self, params, programs):
        return (make_block_step(params, self.config, programs.page),
                make_decide(self.config))
