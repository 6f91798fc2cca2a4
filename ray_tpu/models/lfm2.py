"""LFM2-MoE-style decoder on the TRAIN path: one mixer a layer by
``layer_types`` (a gated short convolution, or grouped-query attention
with per-head q/k norms before the rotary), then a SwiGLU that is dense
in the leading ``num_dense_layers`` layers and ROUTED after them:
sigmoid scores, a choice bias, top-k, no capacity, nothing dropped
(``moe.experts_by_share``, the layer the serving models run, through its
own backward). Tied head, no bias anywhere, no auxiliary loss.

    x_0 = E[tokens]
    layer:  x = x + Mixer(RMS(x; op_norm));  x = x + FFN(RMS(x; ffn_norm))
    conv:   [B | C | z] = u W_in;  c = short_conv(B * z; k);  (C * c) W_out
    attn:   q, k, v = u W_q, u W_k, u W_v;  q, k = RMS_D(q), RMS_D(k);
            rotary on the whole head;  causal softmax(q k^T / sqrt(D)) v;  W_o
    dense:  W_down(silu(W_gate y) * (W_up y))
    routed: s = sigmoid(y_f32 W_r);  S = top-k of s + b;
            w_e = s_e / (sum_{S} s + 1e-6) * route_scale;
            sum_{e in S, held} w_e * expert_e(y)
    logits = RMS(x_L; final_norm) E^T;  mean next-token cross entropy

A chip of an expert-parallel deployment holds ``experts_held = (first,
count)`` of the router's ``n_experts`` and ``vocab_size`` rows of the
vocabulary; ``(0, n_experts)`` and the whole vocabulary is the uncut
model. What the absent experts would add is left out of the sum.

The router's bias ``b`` (``router_bias``) chooses and is no parameter:
the family moves it from the experts' load outside the gradient (that
rule is not run here). It lies in the tree so that a checkpoint carries
it; :func:`frozen_buffers` wraps an optimizer so that it gets no update
and no state.

Layers are of unequal kind, so ``params["layers"]`` is a LIST of dicts
and the forward an unrolled loop of rematerialised blocks (the policy of
``llama.make_block``): five layers compile well inside a cell's set-up,
and a scan over a stacked period would need the dense layer outside it
and the attention layer's leaves padded into the period's.

Serving it is absent (PERF.md section 7): a convolution's state a slot
has no builder here. Measured on ONE device: the parameters carry
logical axes like the other models', but the expert layer's products are
Mosaic kernels, which the compiler cannot partition, and nothing wraps
them in ``shard_map`` yet (the expert exchange, PERF.md section 7).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, moe
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.ops.short_conv import short_conv
from ray_tpu.parallel.sharding import ShardingRules, with_logical_constraint
from ray_tpu.util.profiling import part

Params = Dict[str, Any]
BUFFERS = ("router_bias",)      # leaves of the tree that are no parameter
ROUTE_NORM_EPS = 1e-6           # the family's: in the weights' normalisation


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 256               # the rows of the vocabulary held
    hidden: int = 64
    layer_types: Tuple[str, ...] = ("conv", "full_attention", "conv",
                                    "conv", "conv")
    num_dense_layers: int = 1
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    conv_taps: int = 3                  # ``conv_L_cache``
    mlp_dim: int = 160                  # the dense layers' SwiGLU
    n_experts: int = 8                  # the router's width
    top_k: int = 2
    moe_dim: int = 96                   # an expert's SwiGLU
    experts_held: Tuple[int, int] = (0, 8)
    route_scale: float = 1.0
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    attn_block: int = 512               # ``llama._flash_on_mesh`` reads it

    def __post_init__(self):
        bad = set(self.layer_types) - {"conv", "full_attention"}
        if bad:
            raise ValueError(f"layer_types {sorted(bad)}: 'conv' or "
                             "'full_attention'")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def routed(self, i: int) -> bool:
        return i >= self.num_dense_layers


def _layer_shapes(c: Lfm2Config, i: int) -> Dict[str, tuple]:
    h, D = c.hidden, c.head_dim
    out = {"op_norm": (h,), "ffn_norm": (h,)}
    if c.layer_types[i] == "conv":
        out.update(w_in=(h, 3 * h), conv_k=(h, c.conv_taps), w_out=(h, h))
    else:
        out.update(wq=(h, c.n_heads, D), wk=(h, c.n_kv_heads, D),
                   wv=(h, c.n_kv_heads, D), wo=(c.n_heads, D, h),
                   q_norm=(D,), k_norm=(D,))
    if c.routed(i):
        G, m = c.experts_held[1], c.moe_dim
        out.update(router=(h, c.n_experts), router_bias=(c.n_experts,),
                   we_gate=(G, h, m), we_up=(G, h, m), we_down=(G, m, h))
    else:
        out.update(w_gate=(h, c.mlp_dim), w_up=(h, c.mlp_dim),
                   w_down=(c.mlp_dim, h))
    return out


def param_shapes(config: Lfm2Config) -> Params:
    """The tree of shapes: ``embed`` (tied with the head), ``layers`` a
    list of one dict a layer, ``final_norm``."""
    return {"embed": (config.vocab_size, config.hidden),
            "layers": [_layer_shapes(config, i)
                       for i in range(config.n_layers)],
            "final_norm": (config.hidden,)}


_AXES = {
    "op_norm": ("embed",), "ffn_norm": ("embed",),
    "w_in": ("embed_fsdp", "mlp"), "conv_k": ("embed", None),
    "w_out": ("mlp", "embed_fsdp"),
    "wq": ("embed_fsdp", "heads", "head_dim"),
    "wk": ("embed_fsdp", "kv_heads", "head_dim"),
    "wv": ("embed_fsdp", "kv_heads", "head_dim"),
    "wo": ("heads", "head_dim", "embed_fsdp"),
    "q_norm": (None,), "k_norm": (None,),
    "w_gate": ("embed_fsdp", "mlp"), "w_up": ("embed_fsdp", "mlp"),
    "w_down": ("mlp", "embed_fsdp"),
    "router": ("embed", None), "router_bias": (None,),
    "we_gate": ("expert", "embed_fsdp", "mlp"),
    "we_up": ("expert", "embed_fsdp", "mlp"),
    "we_down": ("expert", "mlp", "embed_fsdp"),
}


def param_logical_axes(config: Lfm2Config) -> Params:
    """Tree matching :func:`init_params` with logical-axis tuples as
    leaves (the names of ``llama.param_logical_axes`` and ``moe``'s)."""
    return {"embed": ("embed_vocab", "embed_fsdp"),
            "layers": [{name: _AXES[name] for name in layer}
                       for layer in param_shapes(config)["layers"]],
            "final_norm": ("embed",)}


def init_params(config: Lfm2Config, key: jax.Array) -> Params:
    """Truncated-normal draws at ``hidden ** -0.5``, projections back
    into the stream scaled down by ``sqrt(2 L)``; norms and the choice
    bias zeros; the router in float32 (its top-k is precision-sensitive)."""
    c = config
    std = c.hidden ** -0.5
    own = {"w_out": std / (2 * c.n_layers) ** 0.5,
           "wo": std / (2 * c.n_layers) ** 0.5,
           "w_down": std / (2 * c.n_layers) ** 0.5,
           "we_down": std / (2 * c.n_layers) ** 0.5,
           "conv_k": c.conv_taps ** -0.5}
    shapes = param_shapes(c)
    leaves, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda t: isinstance(t, tuple))
    vals = []
    for k, (path, shape) in zip(jax.random.split(key, len(leaves)), leaves):
        name = path[-1].key
        if name.endswith("_norm") or name in BUFFERS:
            vals.append(jnp.zeros(shape, c.dtype))
            continue
        w = jax.random.truncated_normal(k, -3, 3, shape, jnp.float32) \
            * own.get(name, std)
        vals.append(w if name == "router" else w.astype(c.dtype))
    return jax.tree.unflatten(treedef, vals)


def frozen_buffers(optimizer, params: Params):
    """``optimizer`` for every parameter of ``params`` (a tree or its
    shapes) and NOTHING for its :data:`BUFFERS`: no update (not even the
    weight decay) and no optimizer state."""
    import optax

    labels = jax.tree_util.tree_map_with_path(
        lambda path, _: "buffer" if path[-1].key in BUFFERS else "parameter",
        params, is_leaf=lambda t: isinstance(t, tuple))
    return optax.multi_transform(
        {"parameter": optimizer, "buffer": optax.set_to_zero()}, labels)


# ------------------------------------------------------------------ the block
def conv_mixer(u, layer: Params):
    """The gated short convolution of a normed input ``u`` (B, S, h)."""
    with part("conv_proj"):
        bcz = jnp.einsum("bse,ef->bsf", u, layer["w_in"].astype(u.dtype))
    b, c, z = jnp.split(bcz, 3, axis=-1)        # in this order
    with part("conv_gate"):
        v = b * z
    conv = short_conv(v, layer["conv_k"])
    with part("conv_gate"):
        g = c * conv
    with part("conv_proj"):
        return jnp.einsum("bse,ef->bsf", g, layer["w_out"].astype(u.dtype))


def attention_mixer(u, layer: Params, cos, sin, config: Lfm2Config,
                    rules: ShardingRules):
    """Causal grouped-query attention of a normed input ``u`` (B, S, h):
    an RMS norm over the head's width on every query and key head (one
    weight vector each, shared by the heads) BEFORE the rotary."""
    c = config
    with part("attn_proj"):
        q = jnp.einsum("bse,ehd->bshd", u, layer["wq"].astype(u.dtype))
        k = jnp.einsum("bse,ehd->bshd", u, layer["wk"].astype(u.dtype))
        v = jnp.einsum("bse,ehd->bshd", u, layer["wv"].astype(u.dtype))
    with part("qk_norm"):
        q = rmsnorm(q, layer["q_norm"], c.norm_eps)
        k = rmsnorm(k, layer["k_norm"], c.norm_eps)
    with part("attn_proj"):
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    q = with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"),
                                rules)
    out = llama._flash_on_mesh(q, k, v, c, rules)
    out = with_logical_constraint(
        out, ("batch", "seq", "heads", "head_dim"), rules)
    with part("attn_proj"):
        return jnp.einsum("bshd,hde->bse", out, layer["wo"].astype(u.dtype))


def routed_ffn(y, layer: Params, config: Lfm2Config):
    """y (B, S, h) after the FFN norm -> ((B, S, h) in y.dtype, the
    partial sum over the experts held; counters of ``moe.COUNTERS``)."""
    B, S, h = y.shape
    out, counters = moe.experts_by_share(
        y.reshape(B * S, h), layer, experts_held=config.experts_held,
        top_k=config.top_k, scale=config.route_scale,
        norm_eps=ROUTE_NORM_EPS)
    with part("expert_layer"):
        return out.astype(y.dtype).reshape(B, S, h), counters


def _block(x, layer: Params, cos, sin, *, i: int, config: Lfm2Config,
           rules: ShardingRules):
    c = config
    conv = c.layer_types[i] == "conv"
    with part("conv_proj" if conv else "attn_proj"):
        u = rmsnorm(x, layer["op_norm"], c.norm_eps)
    mixed = (conv_mixer(u, layer) if conv
             else attention_mixer(u, layer, cos, sin, c, rules))
    with part("conv_proj" if conv else "attn_proj"):
        x = x + mixed
    x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
    if c.routed(i):
        with part("expert_layer"):
            y = rmsnorm(x, layer["ffn_norm"], c.norm_eps)
        out, counters = routed_ffn(y, layer, c)
        with part("expert_layer"):
            x = x + out
    else:
        with part("mlp"):
            x = x + llama.mlp(rmsnorm(x, layer["ffn_norm"], c.norm_eps),
                              layer)
        counters = jnp.zeros(len(moe.COUNTERS), jnp.float32)
    return with_logical_constraint(x, ("batch", "seq", "embed"), rules), \
        counters


def hidden(params: Params, tokens: jax.Array, config: Lfm2Config,
           rules: Optional[ShardingRules] = None):
    """tokens (B, S) int32 -> (the stream after the last layer (B, S, h),
    the routed layers' counters summed, in ``moe.COUNTERS``' order)."""
    c = config
    rules = rules or ShardingRules()
    tokens = with_logical_constraint(tokens, ("batch", "seq"), rules)
    table = with_logical_constraint(
        params["embed"], ("embed_vocab", "embed"), rules)
    with part("embed"):
        x = table.astype(c.dtype)[tokens]
    x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
    cos, sin = rope_frequencies(c.head_dim, tokens.shape[1], c.rope_theta)
    counters = jnp.zeros(len(moe.COUNTERS), jnp.float32)
    for i, layer in enumerate(params["layers"]):
        block = functools.partial(_block, i=i, config=c, rules=rules)
        if c.remat:
            block = jax.checkpoint(
                block, policy=jax.checkpoint_policies.nothing_saveable)
        x, counted = block(x, layer, cos, sin)
        counters = counters + counted
    return x, counters


@part("head")
def logits_of(x, final_norm, table, config: Lfm2Config):
    """Final norm, then the head ``table`` (V, h), which the model ties
    to the embedding: bfloat16 operands, float32 accumulation (the train
    head of ``llama.forward``). -> (B, S, V) float32."""
    x = rmsnorm(x, final_norm, config.norm_eps)
    return jnp.einsum("bse,ve->bsv", x, table.astype(x.dtype),
                      preferred_element_type=jnp.float32)


def forward(params: Params, tokens: jax.Array, config: Lfm2Config,
            rules: Optional[ShardingRules] = None):
    """tokens (B, S) int32 -> (logits (B, S, V) float32 over the rows of
    the vocabulary held, the counters of :func:`hidden`)."""
    rules = rules or ShardingRules()
    x, counters = hidden(params, tokens, config, rules)
    logits = logits_of(x, params["final_norm"], params["embed"], config)
    return with_logical_constraint(
        logits, ("batch", "seq", "vocab"), rules), counters


def loss_fn(params: Params, batch: Dict[str, jax.Array], config: Lfm2Config,
            rules: Optional[ShardingRules] = None, mesh=None):
    """Mean next-token cross entropy in float32, no auxiliary term: the
    contract of ``llama.loss_fn``, so ``training.make_train_step`` takes
    it as it is. ``metrics["moe"]``: the step's counters by name, summed
    over the routed layers (``expert_layer_calls`` counts them)."""
    del mesh
    tokens = batch["tokens"]
    logits, counters = forward(params, tokens, config, rules)
    loss, metrics = llama.next_token_loss(logits, tokens, batch.get("mask"))
    metrics["moe"] = dict(zip(moe.COUNTERS, counters))
    return loss, metrics
