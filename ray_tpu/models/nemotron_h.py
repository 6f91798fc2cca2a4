"""A decoder whose layers have ONE mixer each, a Mamba-2 state-space
mixer, an attention or a latent expert layer by a pattern (the language
model of the ``nemotron_h`` family), on the paged serving path.

``pattern`` is a string over ``M`` (Mamba-2), ``E`` (experts) and ``*``
(attention); layer ``l`` is ``x <- x + mixer_l(RMSNorm(x))``, then a
final norm and an untied head. A norm's stored weight scales by ``1 +
w``.

*Mamba-2* (``H`` heads of ``P``, ``d_in = H P``; ``G`` groups of ``N``
state columns; a causal depthwise convolution of ``K`` taps over ``d_c =
d_in + 2 G N`` channels)::

    [z | xBC | dt_raw] = u W_in
    xBC_t <- silu(b_c + sum_k w_c[k] xBC_{t-K+1+k})     zeros before 0
    x (H, P), B (G, N), C (G, N) = split(xBC)          head h: group h // (H / G)
    dt = softplus(dt_raw + dt_bias);  A = -exp(A_log)   a scalar a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t    (P, N) a head, float32
    y_t = S_t C_t + D x_t
    y <- RMSNorm over each of the G runs of d_in / G channels of y silu(z)
    out = y W_out

Its state a sequence is ``S`` (H P N float32) and the last ``K - 1`` raw
``xBC`` columns, however long the sequence is: no block of any pool but
one row a SLOT of ``cache["ssm_state"]`` (M-layers, slots, G, N, H / G x
P: the layout of :mod:`ray_tpu.ops.pallas.ssm_decode_update`) and
``cache["conv_state"]`` (M-layers, slots, K - 1, d_c). A prefill starts
from zero and writes the slot's rows as they stand after ``true_len``
tokens, whatever the padded length (positions past it get ``dt = 0``,
which leaves ``S`` as it is, and the convolution's columns are read at
``true_len``); a decode step updates the rows of running slots only, in
place. The prefill computes the recurrence in chunks (state-space
duality: inside a chunk ``((C B^T) * L) X`` with ``L_ij = exp(sum_{j<k<=i}
dt_k A)``, a chunk's closing state carried to the next by a scan), all
of it batched matrix products in ``jax.numpy``.

*Attention*: ``n_heads`` query heads over ``n_kv_heads`` KV heads of
``head_dim``, causal, scale ``head_dim ** -0.5``, no bias and NO
position term (the family publishes its attention layers without one).
Its keys and values go through the dense decoder's pool, allocator and
decode kernel (:mod:`ray_tpu.models.paged_cache`).

*Experts*, latent: the router reads the hidden state, the experts a
projection of it to ``latent`` (``w_fc1``); an expert is ``relu(l U_e)
** 2 D_e``; the weighted sum over the experts HELD here
(:func:`ray_tpu.models.moe.experts_by_share`, sigmoid scores with a
correction bias) goes back up through ``w_fc2``, beside an ungated
shared expert on the hidden state itself.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ray_tpu.models import moe
from ray_tpu.models import paged_cache as pc
from ray_tpu.models.decoding import _bind_params
from ray_tpu.models.paged_cache import KVStateManager, PagedConfig
from ray_tpu.ops.attention import mha_reference
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.pallas.paged_decode_attention import paged_decode
from ray_tpu.ops.pallas.ssm_decode_update import ssm_decode
from ray_tpu.util.profiling import part

Params = Dict[str, Any]
F32 = jnp.float32
COUNTERS = moe.COUNTERS + ("ssm_slots_live", "ssm_layer_calls")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 256
    hidden: int = 64
    pattern: str = "MEMEMEM*EME"    # one mixer a layer
    ssm_heads: int = 8              # H
    ssm_head_dim: int = 8           # P
    ssm_groups: int = 2             # G: heads of a group share B and C
    ssm_state: int = 16             # N
    conv_kernel: int = 4            # K
    chunk: int = 16                 # the prefill's chunk
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    n_experts: int = 16             # the router's width
    top_k: int = 4
    experts_held: Tuple[int, int] = (0, 16)      # (first, count) here
    latent: int = 32                # the experts' input and output width
    expert_dim: int = 24
    shared_dim: int = 48
    routed_scale: float = 5.0
    norm_eps: float = 1e-5
    max_seq: int = 2048
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if set(self.pattern) - set("ME*"):
            raise ValueError(f"pattern {self.pattern!r}: M, E and * only")
        if self.ssm_heads % self.ssm_groups or self.n_heads % self.n_kv_heads:
            raise ValueError("heads not a multiple of their groups")

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def state_bytes_per_slot(self) -> int:
        """Recurrent and convolution state of one sequence over every
        state-space layer."""
        return self.pattern.count("M") * (
            4 * self.d_inner * self.ssm_state
            + jnp.dtype(self.dtype).itemsize
            * (self.conv_kernel - 1) * self.conv_dim)

    def serving_model(self):
        return NemotronHServing(self)


# ----------------------------------------------------------------- weights
def param_shapes(cfg: NemotronHConfig) -> Params:
    """The tree the builders take, as shapes: ``layers`` a LIST, one dict
    a layer, by its character of the pattern. ``conv_w`` is (K, d_c):
    tap k of every channel is one lane-dense row."""
    c = cfg
    h, H, G, N = c.hidden, c.ssm_heads, c.ssm_groups, c.ssm_state
    held = c.experts_held[1]
    kinds = {
        "M": {"norm": (h,),
              "w_in": (h, 2 * c.d_inner + 2 * G * N + H),
              "conv_w": (c.conv_kernel, c.conv_dim), "conv_b": (c.conv_dim,),
              "dt_bias": (H,), "A_log": (H,), "D": (H,),
              "gate_norm": (c.d_inner,), "w_out": (c.d_inner, h)},
        "*": {"norm": (h,), "wq": (h, c.n_heads, c.head_dim),
              "wk": (h, c.n_kv_heads, c.head_dim),
              "wv": (h, c.n_kv_heads, c.head_dim),
              "wo": (c.n_heads, c.head_dim, h)},
        "E": {"norm": (h,), "router": (h, c.n_experts),
              "router_bias": (c.n_experts,),
              "w_fc1": (h, c.latent), "w_fc2": (c.latent, h),
              "ws_up": (h, c.shared_dim), "ws_down": (c.shared_dim, h),
              "we_up": (held, c.latent, c.expert_dim),
              "we_down": (held, c.expert_dim, c.latent)}}
    return {"embed": (c.vocab_size, h),
            "layers": [dict(kinds[k]) for k in c.pattern],
            "final_norm": (h,), "lm_head": (h, c.vocab_size)}


def param_stds(cfg: NemotronHConfig):
    """(default, {leaf: its own}): a matrix at its fan-in ** -0.5; the
    embedding at 1, a token's own row of the stream's order; every
    projection back into the residual stream scaled down by ``sqrt(2 L)``;
    an expert's down projection ``routed_scale`` times smaller, which the
    routed sum is multiplied by; norms at 0.1. ``dt_bias`` and ``D`` at
    1, ``A_log`` at 2: with ``dt_raw`` of order one ``dt`` is 0.1-2.4 and
    ``-A`` 0.04-27, so some heads carry a prompt for hundreds of tokens
    and some forget it within one."""
    c = cfg
    std = c.hidden ** -0.5
    back = (2 * len(c.pattern)) ** -0.5
    return std, {"embed": 1.0,
                 "norm": 0.1, "gate_norm": 0.1, "final_norm": 0.1,
                 "conv_w": 0.5, "conv_b": 0.1, "dt_bias": 1.0, "A_log": 2.0,
                 "D": 1.0, "router_bias": 0.01,
                 "w_out": c.d_inner ** -0.5 * back,
                 "wo": (c.n_heads * c.head_dim) ** -0.5 * back,
                 "w_fc2": c.latent ** -0.5 * back,
                 "ws_down": c.shared_dim ** -0.5 * back,
                 "we_up": c.latent ** -0.5,
                 "we_down": c.expert_dim ** -0.5 / c.routed_scale}


def init_params(cfg: NemotronHConfig, key: jax.Array) -> Params:
    from ray_tpu.models.serving import init_from_shapes

    return init_from_shapes(param_shapes(cfg), key, *param_stds(cfg),
                            cfg.dtype)


# ------------------------------------------------------------------- cache
def init_cache(cfg: NemotronHConfig, page: PagedConfig, num_slots: int):
    c = cfg
    kv = (c.pattern.count("*"), page.num_blocks, page.block_size,
          c.n_kv_heads * c.head_dim)
    nM, G = c.pattern.count("M"), c.ssm_groups
    return {"k": jnp.zeros(kv, c.dtype), "v": jnp.zeros(kv, c.dtype),
            "length": jnp.zeros((num_slots,), jnp.int32),
            "counters": jnp.zeros((len(COUNTERS),), F32),
            "ssm_state": jnp.zeros(
                (nM, num_slots, G, c.ssm_state, c.d_inner // G), F32),
            "conv_state": jnp.zeros(
                (nM, num_slots, c.conv_kernel - 1, c.conv_dim), c.dtype)}


def make_manager(cfg: NemotronHConfig, page: PagedConfig,
                 num_slots: int) -> KVStateManager:
    """One kind of blocks (the attention layers' keys and values), and
    the recurrent state as the kind that is none."""
    return KVStateManager(
        {"full": (page, None)}, num_slots,
        slot_state={"ssm_state": cfg.state_bytes_per_slot})


# ------------------------------------------------------------------ mixers
@part("ssm_proj")
def _in_proj(x, layer, cfg):
    """x (T, h) -> z (T, d_in), raw xBC (T, d_c) in cfg.dtype, dt_raw
    (T, H) float32: ``u W_in`` as THREE products, one against each run
    of the stored matrix's columns. Made as one product and split, the
    parts are read at four places of a decode layer on both sides of the
    state update's kernel, and the chip's compiler, rather than keep the
    (192, 18,560) result alive, computed the whole product again for
    each of them: 18 reads of a 152 MB ``w_in`` a step where 5 are
    needed (my chip run, PR 40). With a product a part, each fuses into
    its own consumer and reads its own columns only; do not fuse them
    back."""
    u = rmsnorm(x, layer["norm"], cfg.norm_eps)
    w = layer["w_in"]
    z_end, xbc_end = cfg.d_inner, cfg.d_inner + cfg.conv_dim

    def proj(lo, hi):
        return jnp.dot(u, w[:, lo:hi].astype(u.dtype),
                       preferred_element_type=F32)

    return (proj(0, z_end).astype(x.dtype),
            proj(z_end, xbc_end).astype(x.dtype),
            proj(xbc_end, w.shape[1]))


@part("ssm_conv")
def _conv(window, layer, dtype):
    """``silu(b + sum_k w[k] window[..., k, :])``: window (..., K, d_c)."""
    acc = jnp.sum(window.astype(F32) * layer["conv_w"].astype(F32), axis=-2)
    return jax.nn.silu(acc + layer["conv_b"].astype(F32)).astype(dtype)


def _split_xbc(xbc, cfg):
    """Convolved xBC (T, d_c) -> x (T, G, W), B (T, G, N), C (T, G, N),
    ``W = H / G x P``: head after head of a group."""
    G, N = cfg.ssm_groups, cfg.ssm_state
    x, b, c = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + G * N], axis=-1)
    T = xbc.shape[0]
    return (x.reshape(T, G, -1), b.reshape(T, G, N), c.reshape(T, G, N))


def _dt_and_decay(dt_raw, layer):
    """dt (T, H) and dt A (T, H), float32."""
    dt = jax.nn.softplus(dt_raw + layer["dt_bias"].astype(F32))
    return dt, -dt * jnp.exp(layer["A_log"].astype(F32))


@part("ssm_gate_norm")
def _gate_norm(y, x, z, layer, cfg):
    """``y + D x``, gated by ``silu(z)``, then an RMS norm over each of
    the G runs of channels. y (T, G, W) float32, x (T, G, W), z (T,
    d_in) -> (T, d_in) in cfg.dtype."""
    T, G, W = y.shape
    D = jnp.repeat(layer["D"].astype(F32), cfg.ssm_head_dim).reshape(G, W)
    y = (y + D * x.astype(F32)) * jax.nn.silu(
        z.astype(F32)).reshape(T, G, W)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + cfg.norm_eps)
    return (y.reshape(T, -1)
            * (1.0 + layer["gate_norm"].astype(F32))).astype(cfg.dtype)


@part("ssm_proj")
def _out_proj(x, y, layer):
    return x + y @ layer["w_out"].astype(y.dtype)


@part("ssm_scan")
def ssd_chunked(x, dt, dta, b, c, chunk: int):
    """The recurrence over a whole sequence from a zero state, in chunks:
    x (T, G, W) with ``W = heads a group x P``, dt and ``dta = dt A``
    (T, H) float32, b, c (T, G, N); T a multiple of the chunk (or
    shorter than one). -> (y (T, G, W) float32 WITHOUT ``D x``, the
    closing state (G, N, W) float32 in the cache's layout). Matrix
    products take x.dtype operands and accumulate in float32."""
    T, G, W = x.shape
    H = dt.shape[1]
    hb, N = H // G, b.shape[-1]
    P = W // hb
    L = min(chunk, T)
    ct = x.dtype

    def chunks(a, *shape):
        return a.reshape(T // L, L, *shape)

    xdt = chunks(x.astype(F32).reshape(T, G, hb, P)
                 * dt.reshape(T, G, hb, 1), G, hb, P)
    seen = jnp.tril(jnp.ones((L, L), bool))[:, :, None, None]

    def body(S, inputs):         # S (G, N, hb, P) float32: the cache's
        xdt, dta, b, c = inputs
        cum = jnp.cumsum(dta, axis=0)                         # (L, G, hb)
        cb = jnp.einsum("lgn,sgn->gls", c, b, preferred_element_type=F32)
        # L_ij = exp(sum_{j<k<=i} dt_k A): the exponent masked first, so
        # nothing above the diagonal is ever exponentiated
        decay = jnp.exp(jnp.where(seen, cum[:, None] - cum[None, :],
                                  -jnp.inf))                  # (L, L, G, hb)
        m = (cb[:, None] * decay.transpose(2, 3, 0, 1)).astype(ct)
        y = jnp.einsum("gkls,sgkp->lgkp", m, xdt.astype(ct),
                       preferred_element_type=F32)
        y = y + jnp.einsum("lgn,gnkp->lgkp", c, S.astype(ct),
                           preferred_element_type=F32
                           ) * jnp.exp(cum)[..., None]
        tail = jnp.exp(cum[-1][None] - cum)[..., None]        # to the end
        S = (jnp.exp(cum[-1])[:, None, :, None] * S
             + jnp.einsum("lgkp,lgn->gnkp", (xdt * tail).astype(ct), b,
                          preferred_element_type=F32))
        return S, y

    S, y = jax.lax.scan(
        body, jnp.zeros((G, N, hb, P), F32),
        (xdt, chunks(dta, G, hb), chunks(b, G, N), chunks(c, G, N)))
    return y.reshape(T, G, W), S.reshape(G, N, W)


def _mamba_decode(x, layer, cfg, ssm, conv, li, active):
    """One token a slot: x (B, h) -> (x, ssm, conv), the states of layer
    ``li`` updated in place for the slots that run."""
    z, xbc, dt_raw = _in_proj(x, layer, cfg)
    with part("ssm_conv"):
        # the window is MADE before the state is written from it: left to
        # fuse, the write is a copy of columns 1.. onto columns 0.. inside
        # the donated buffer, and the program the chip's compiler makes of
        # that at 192 slots reads columns it has already overwritten (the
        # first layer's two carried columns came out as noise, every step:
        # my chip run, PR 39; at 8 slots and on the CPU they did not)
        window = jax.lax.optimization_barrier(
            jnp.concatenate([conv[li], xbc[:, None]], axis=1))
        conv = conv.at[li].set(jnp.where(active[:, None, None],
                                         window[:, 1:], window[:, :-1]))
    xs, b, c = _split_xbc(_conv(window, layer, cfg.dtype), cfg)
    with part("ssm_update"):
        dt, dta = _dt_and_decay(dt_raw, layer)
        P = cfg.ssm_head_dim
        xdt = xs.astype(F32) * jnp.repeat(dt, P, axis=-1).reshape(xs.shape)
        decay = jnp.repeat(jnp.exp(dta), P, axis=-1).reshape(xs.shape)
    ssm, y = ssm_decode(ssm, li, xdt, decay, b.astype(F32), c.astype(F32),
                        active)
    return _out_proj(x, _gate_norm(y, xs, z, layer, cfg), layer), ssm, conv


def _mamba_prefill(x, layer, cfg, ssm, conv, li, slot, true_len, valid):
    """One padded prompt: x (P, h) -> (x, ssm, conv), row ``slot`` of
    layer ``li``'s states as after ``true_len`` tokens from zero."""
    K = cfg.conv_kernel
    z, xbc, dt_raw = _in_proj(x, layer, cfg)
    with part("ssm_conv"):
        padded = jnp.concatenate(
            [jnp.zeros((K - 1, cfg.conv_dim), xbc.dtype), xbc])
        window = jnp.stack([padded[k:k + x.shape[0]] for k in range(K)],
                           axis=1)                            # (P, K, d_c)
        # the last K - 1 raw columns of the TRUE prompt
        conv = conv.at[li, slot].set(jax.lax.dynamic_slice_in_dim(
            padded, true_len, K - 1))
    xs, b, c = _split_xbc(_conv(window, layer, cfg.dtype), cfg)
    with part("ssm_scan"):
        dt, dta = _dt_and_decay(dt_raw, layer)
        # a position past the prompt's end leaves the state as it is
        dt = jnp.where(valid[:, None], dt, 0.0)
        dta = jnp.where(valid[:, None], dta, 0.0)
    y, S = ssd_chunked(xs, dt, dta, b, c, cfg.chunk)
    with part("ssm_scan"):
        # the scan's carry is laid out for its products (N innermost);
        # left free, the compiler lays the WHOLE cache out like it around
        # this one row's write, a copy of every slot's state each way
        ssm = ssm.at[li, slot].set(with_layout_constraint(
            S, Layout(major_to_minor=(0, 1, 2))))
    return _out_proj(x, _gate_norm(y, xs, z, layer, cfg), layer), ssm, conv


@part("attn_proj")
def _qkv(x, layer, cfg):
    """x (T, h) -> q (T, H, D), k, v (T, KV, D): no position term."""
    u = rmsnorm(x, layer["norm"], cfg.norm_eps)
    return (jnp.einsum("te,ehd->thd", u, layer["wq"].astype(u.dtype)),
            jnp.einsum("te,ehd->thd", u, layer["wk"].astype(u.dtype)),
            jnp.einsum("te,ehd->thd", u, layer["wv"].astype(u.dtype)))


@part("kv_store")
def _store_blocks(pools, li, dest, valid, k, v):
    """A padded prompt's keys and values (P, KV, D) into the blocks
    ``dest`` (nblk,) of layer ``li``, block by block, each a token's KV
    heads side by side as the pool holds them. Not one scatter of all
    the blocks (``paged_cache.store_kv_rows``): while the pool's rows
    were (KV, D) the compiler laid a scatter's operand out with the
    block's tokens, not its 2 heads, on the sublanes, and copied the
    WHOLE pool there and back around the write (0.6 ms each way for each
    of k and v at the cell's pool; my chip run, PR 39). Kept as measured
    then; whether the lane-dense pool still needs it was not asked."""
    kc, vc = pools
    bs = kc.shape[2]

    def put(pool, rows, j):
        block = pc.fold_heads(jnp.where(
            valid[j * bs:(j + 1) * bs, None, None],
            rows[j * bs:(j + 1) * bs], 0.0))
        return jax.lax.dynamic_update_slice(
            pool, block.astype(pool.dtype)[None, None],
            (li, dest[j], 0, 0))

    for j in range(dest.shape[0]):
        kc, vc = put(kc, k, j), put(vc, v, j)
    return kc, vc


@part("attn_proj")
def _attn_out(x, out, layer):
    return x + jnp.einsum("thd,hde->te", out, layer["wo"].astype(x.dtype))


def _experts(x, layer, cfg, valid, kernel_name="grouped_expert_matmul"):
    """x (T, h) -> (x + the expert layer's part computed here, counters)."""
    with part("mlp"):
        u = rmsnorm(x, layer["norm"], cfg.norm_eps)
    with part("latent_proj"):
        latent = u @ layer["w_fc1"].astype(u.dtype)
    routed, counters = moe.experts_by_share(
        u, layer, experts_held=cfg.experts_held, top_k=cfg.top_k,
        scale=cfg.routed_scale, valid=valid, kernel_name=kernel_name,
        x_experts=latent)
    with part("latent_proj"):
        up = routed.astype(u.dtype) @ layer["w_fc2"].astype(u.dtype)
    shared = moe.shared_expert(u, layer)
    with part("mlp"):
        return x + up + shared, counters


@part("head")
def _head(x, params, cfg):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x.astype(F32) @ params["lm_head"].astype(F32)


def _counters(expert_counters, n_ssm_layers: int, live):
    """The expert layers' five, then the slots whose state the step's
    state-space layers updated (summed over those layers) and their
    calls."""
    n = jnp.float32(n_ssm_layers)
    return jnp.concatenate([expert_counters, jnp.stack([n * live, n])])


# ---------------------------------------------------------------- programs
def make_decode_step(params: Params, cfg: NemotronHConfig, page: PagedConfig):
    """step(cache, tables {"full": (B, MBS) i32}, tokens (B,), active
    (B,) bool) -> (cache, logits (B, vocab) f32)."""
    bs = page.block_size
    scale = cfg.head_dim ** -0.5

    def step(params, cache, tables, tokens, active):
        lengths = cache["length"]
        table = tables["full"]
        with part("embed"):
            x = params["embed"].astype(cfg.dtype)[tokens]        # (B, h)
        with part("kv_store"):
            blk = jnp.where(
                active, table[jnp.arange(tokens.shape[0]), lengths // bs], 0)
            off = lengths % bs
            att_len = jnp.where(active, lengths + 1, 0)
        work = pc._decode_work(att_len, page)
        pools = cache["k"], cache["v"]
        ssm, conv = cache["ssm_state"], cache["conv_state"]
        index = dict.fromkeys("ME*", 0)
        counters = jnp.zeros((len(moe.COUNTERS),), F32)
        for kind, layer in zip(cfg.pattern, params["layers"]):
            li, index[kind] = index[kind], index[kind] + 1
            if kind == "M":
                x, ssm, conv = _mamba_decode(x, layer, cfg, ssm, conv, li,
                                             active)
            elif kind == "*":
                q, k, v = _qkv(x, layer, cfg)
                pools = pc.store_kv_rows(pools, (li, blk, off),
                                         pc.fold_heads(k), pc.fold_heads(v))
                out = paged_decode(q[:, None], *pools, li, table, att_len,
                                   scale=scale, work=work)
                x = _attn_out(x, out[:, 0], layer)
            else:
                x, c = _experts(x, layer, cfg, active)
                counters = counters + c
        new = {"k": pools[0], "v": pools[1],
               "length": jnp.where(active, lengths + 1, lengths),
               "counters": _counters(counters, index["M"],
                                     jnp.sum(active).astype(F32)),
               "ssm_state": ssm, "conv_state": conv}
        return new, _head(x, params, cfg)

    return _bind_params(jax.jit(step, donate_argnums=(1,)), params)


def make_prefill(params: Params, cfg: NemotronHConfig, page: PagedConfig):
    """prefill(cache, table_rows {"full": (MBS,) i32}, tokens (1, P)
    padded, true_len, slot) -> (cache, last_logits (vocab,) f32). P a
    multiple of the block size and of the chunk (or shorter than one).
    The slot's recurrent and convolution state are written whole."""
    bs = page.block_size

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("pad_len",))
    def prefill(params, cache, table_rows, tokens, true_len, slot,
                pad_len: int):
        nblk = pad_len // bs
        with part("embed"):
            x = params["embed"].astype(cfg.dtype)[tokens[0]]     # (P, h)
        valid = jnp.arange(pad_len) < true_len
        with part("kv_store"):
            dest = jnp.where(jnp.arange(nblk) * bs < true_len,
                             table_rows["full"][:nblk], 0)
        pools = cache["k"], cache["v"]
        ssm, conv = cache["ssm_state"], cache["conv_state"]
        index = dict.fromkeys("ME*", 0)
        counters = jnp.zeros((len(moe.COUNTERS),), F32)
        for kind, layer in zip(cfg.pattern, params["layers"]):
            li, index[kind] = index[kind], index[kind] + 1
            if kind == "M":
                x, ssm, conv = _mamba_prefill(x, layer, cfg, ssm, conv, li,
                                              slot, true_len, valid)
            elif kind == "*":
                q, k, v = _qkv(x, layer, cfg)
                out = mha_reference(q[None], k[None], v[None], causal=True)
                pools = _store_blocks(pools, li, dest, valid, k, v)
                x = _attn_out(x, out[0], layer)
            else:
                x, c = _experts(x, layer, cfg, valid,
                                "grouped_expert_matmul_prefill")
                counters = counters + c
        new = {"k": pools[0], "v": pools[1],
               "length": cache["length"].at[slot].set(true_len),
               "counters": _counters(counters, index["M"], jnp.float32(1.0)),
               "ssm_state": ssm, "conv_state": conv}
        return new, _head(x[jnp.maximum(true_len - 1, 0)], params, cfg)

    def call(cache, table_rows, tokens, true_len, slot):
        pad_len = tokens.shape[1]
        if pad_len % bs or (pad_len > cfg.chunk and pad_len % cfg.chunk):
            raise ValueError(
                f"padded prompt {pad_len} not a multiple of block_size "
                f"{bs} and of the chunk {cfg.chunk}")
        return prefill(params, cache,
                       {"full": jnp.asarray(table_rows["full"], jnp.int32)},
                       tokens,
                       jnp.asarray(true_len, jnp.int32),
                       jnp.asarray(slot, jnp.int32), pad_len=pad_len)

    call.jitted = prefill
    return call


# ------------------------------------------------- what the engine is given
class NemotronHServing:
    """The model as :class:`ray_tpu.serve.llm.LLMEngine` takes it
    (:mod:`ray_tpu.models.serving`)."""

    def __init__(self, config: NemotronHConfig):
        self.config = config

    def init_params(self, key):
        return init_params(self.config, key)

    def paged(self, params, *, num_slots: int, max_seq: int,
              block_size: int, pool_tokens: int):
        from ray_tpu.models.serving import PagedPrograms

        page = PagedConfig(num_blocks=1 + -(-pool_tokens // block_size),
                           block_size=block_size, max_seq=max_seq)  # +null
        return PagedPrograms(
            alloc=make_manager(self.config, page, num_slots),
            cache=init_cache(self.config, page, num_slots),
            prefill=make_prefill(params, self.config, page),
            decode=make_decode_step(params, self.config, page),
            page=page, counters=COUNTERS)
