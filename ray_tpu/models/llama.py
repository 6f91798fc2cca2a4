"""Llama-family decoder-only transformer, TPU-first.

Design (vs the reference framework, which has no in-tree model — its LLM
serving delegates to vLLM, reference ``python/ray/llm/_internal/serve/
deployments/llm/vllm/vllm_models.py:206-220``):

- **Pure functional**: params are a plain pytree of ``jax.Array``; every
  entry has a parallel tree of *logical axis names*
  (:func:`param_logical_axes`) consumed by ``ray_tpu.parallel.sharding`` —
  one rule table swap re-lays-out the model (fsdp / tp / both).
- **Scan over layers**: layer params are stacked on a leading ``layers``
  axis and the block runs under ``jax.lax.scan`` + ``jax.checkpoint`` —
  one compiled block regardless of depth, O(1) compile time in n_layers,
  remat bounds activation HBM.
- **bfloat16 activations, float32 einsum accumulation** — MXU-native.
- **Flash attention** via ``ray_tpu.ops.attention`` (Pallas kernel on TPU).
- **GQA** (n_kv_heads < n_heads) as in Llama-3.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.parallel.sharding import ShardingRules, with_logical_constraint
from ray_tpu.util.profiling import part

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    mlp_dim: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16       # activation / weight dtype
    remat: bool = True              # checkpoint each layer under scan
    # "nothing" (max recompute, min HBM), "dots" (save matmul outputs —
    # fewer recomputed FLOPs, more HBM), "none" alias of remat=False
    remat_policy: str = "nothing"
    # tile of the blockwise XLA flash backward (off-TPU); the Pallas kernels
    # choose their blocks from the call's shapes
    attn_block: int = 512
    # Ring/sequence-parallel attention: set by the trainer when sp > 1.
    sp_axis: Optional[str] = None

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    def flops_per_token(self, seq: Optional[int] = None) -> float:
        """Training FLOPs/token: 6·N (fwd+bwd matmuls) + causal attention
        (per layer fwd: QKᵀ and P·V are 4·S·d, halved by causality → 2·S·d;
        ×3 for fwd+bwd → 6·L·S·d). The single source of truth for MFU."""
        seq = self.max_seq if seq is None else seq
        return (6.0 * self.num_params()
                + 6.0 * self.n_layers * seq * self.q_dim)

    def num_params(self) -> int:
        p = self.vocab_size * self.hidden                        # embed
        per_layer = (
            self.hidden * self.q_dim                             # q
            + 2 * self.hidden * self.n_kv_heads * self.head_dim  # k, v
            + self.q_dim * self.hidden                           # out
            + 3 * self.hidden * self.mlp_dim                     # gate/up/down
            + 2 * self.hidden                                    # norms
        )
        p += self.n_layers * per_layer + self.hidden             # final norm
        if not self.tie_embeddings:
            p += self.hidden * self.vocab_size                   # lm head
        return p


# Named configs. tiny/debug sizes keep CI on the 8-device CPU mesh fast.
CONFIGS: Dict[str, LlamaConfig] = {
    "debug": LlamaConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, head_dim=16, mlp_dim=128, max_seq=128,
                         dtype=jnp.float32, remat=False),
    "tiny": LlamaConfig(vocab_size=32000, hidden=512, n_layers=4, n_heads=8,
                        n_kv_heads=4, head_dim=64, mlp_dim=1408, max_seq=2048),
    "1b": LlamaConfig(vocab_size=128256, hidden=2048, n_layers=16, n_heads=32,
                      n_kv_heads=8, head_dim=64, mlp_dim=8192, max_seq=8192),
    "8b": LlamaConfig(),  # Llama-3-8B shapes
    "70b": LlamaConfig(hidden=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                       head_dim=128, mlp_dim=28672),
}


def param_logical_axes(config: LlamaConfig) -> Params:
    """Tree matching :func:`init_params` with logical-axis tuples as leaves."""
    axes = {
        # The table's vocab dim stays unsharded by default (embed_vocab rule):
        # a gather over a tp-sharded vocab axis forces XLA into
        # replicate-then-repartition ("involuntary full rematerialization").
        "embed": ("embed_vocab", "embed_fsdp"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed_fsdp", "heads", "head_dim"),
            "wk": ("layers", "embed_fsdp", "kv_heads", "head_dim"),
            "wv": ("layers", "embed_fsdp", "kv_heads", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed_fsdp"),
            "mlp_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed_fsdp", "mlp"),
            "w_up": ("layers", "embed_fsdp", "mlp"),
            "w_down": ("layers", "mlp", "embed_fsdp"),
        },
        "final_norm": ("embed",),
    }
    if not config.tie_embeddings:
        axes["lm_head"] = ("embed_fsdp", "vocab")
    return axes


def init_params(config: LlamaConfig, key: jax.Array) -> Params:
    """Truncated-normal init, scaled residual projections (GPT-2 style)."""
    c = config
    k = iter(jax.random.split(key, 16))
    dt = c.dtype

    def tn(key, shape, std):
        return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)
                * std).astype(dt)

    std = c.hidden ** -0.5
    out_std = std / (2 * c.n_layers) ** 0.5
    L = c.n_layers
    params: Params = {
        # hidden^-0.5 keeps tied-head logits ~unit-variance at init.
        "embed": tn(next(k), (c.vocab_size, c.hidden), std),
        "layers": {
            "attn_norm": jnp.zeros((L, c.hidden), dt),
            "wq": tn(next(k), (L, c.hidden, c.n_heads, c.head_dim), std),
            "wk": tn(next(k), (L, c.hidden, c.n_kv_heads, c.head_dim), std),
            "wv": tn(next(k), (L, c.hidden, c.n_kv_heads, c.head_dim), std),
            "wo": tn(next(k), (L, c.n_heads, c.head_dim, c.hidden), out_std),
            "mlp_norm": jnp.zeros((L, c.hidden), dt),
            "w_gate": tn(next(k), (L, c.hidden, c.mlp_dim), std),
            "w_up": tn(next(k), (L, c.hidden, c.mlp_dim), std),
            "w_down": tn(next(k), (L, c.mlp_dim, c.hidden), out_std),
        },
        "final_norm": jnp.zeros((c.hidden,), dt),
    }
    if not c.tie_embeddings:
        params["lm_head"] = tn(next(k), (c.hidden, c.vocab_size), std)
    return params


# Where a stacked projection (L, E, heads, D) lies in a serving device's
# memory, major to minor: heads outside is what :func:`qkv`'s products
# read, so a layer's slice is multiplied where it lies (``wq`` / ``wk``:
# ``E`` on the sublanes; ``wv``, whose product is not rotated: ``E`` on the
# lanes). In the default order the chip's compiler stages each slice and
# copies it into that order first, every layer of every step; ``wv``'s
# slice is still staged in a decode step, in any of the six orders, and
# in this one it is not copied again (PERF.md section 6, PR 53).
SERVING_LAYOUT = {"wq": (0, 2, 1, 3), "wk": (0, 2, 1, 3), "wv": (0, 2, 3, 1)}


def serving_layout(params: Params) -> Params:
    """``params`` with ``layers.wq`` / ``wk`` / ``wv`` laid out on their
    own devices as :data:`SERVING_LAYOUT` says: shapes and values as they
    were, every other leaf the caller's own. The three leaves given are
    DONATED, one at a time, so one leaf at the most exists twice and the
    caller's buffers of them are gone. A program jitted without
    ``in_shardings`` compiles for the layout its argument arrives in."""
    from jax.experimental.layout import Format, Layout

    layers = dict(params["layers"])
    for name, order in SERVING_LAYOUT.items():
        w = layers[name]
        layers[name] = jax.device_put(
            w, Format(Layout(major_to_minor=order), w.sharding), donate=True)
    return {**params, "layers": layers}


# The pieces of the block: the train forward below and the serving programs
# (``models/decoding.py::dense_block``) are made of the same ones.
@part("attn_proj")
def qkv(h, layer, cos, sin, positions=None):
    """The three projections of a normed input ``h`` (B, S, E), queries
    and keys rotated to ``positions`` ((B, S) or (1, S); None: 0..S-1).
    -> q (B, S, H, D), k and v (B, S, KV, D)."""
    q = jnp.einsum("bse,ehd->bshd", h, layer["wq"].astype(h.dtype))
    k = jnp.einsum("bse,ehd->bshd", h, layer["wk"].astype(h.dtype))
    v = jnp.einsum("bse,ehd->bshd", h, layer["wv"].astype(h.dtype))
    return (apply_rope(q, cos, sin, positions),
            apply_rope(k, cos, sin, positions), v)


@part("mlp")
def mlp(h, layer):
    """Gate / up / down of a normed input ``h`` (B, S, E)."""
    g = jnp.einsum("bse,em->bsm", h, layer["w_gate"].astype(h.dtype))
    u = jnp.einsum("bse,em->bsm", h, layer["w_up"].astype(h.dtype))
    return jnp.einsum("bsm,me->bse", jax.nn.silu(g) * u,
                      layer["w_down"].astype(h.dtype))


@part("embed")
def embed(params: Params, tokens, c: LlamaConfig):
    """Serving's embedding: one device, no constraints (the train
    forward places its own around the gather)."""
    return params["embed"].astype(c.dtype)[tokens]


@part("head")
def logits_f32(x, params: Params, c: LlamaConfig, row=None):
    """Serving's head: final norm over all of ``x`` (..., E), then ``x[row]``
    (a prefill's last valid position; None: every row) times the tied or
    untied head -> (..., vocab) float32.

    float32 OPERANDS, on purpose unlike :func:`forward`'s head (bfloat16
    operands, float32 accumulation, for the MXU's rate over B x S rows):
    serving multiplies one row a sequence, so the rate buys nothing and
    the logits that are sampled from are not rounded."""
    x = rmsnorm(x, params["final_norm"], c.norm_eps)
    if row is not None:
        x = x[row]
    head = params["embed"].T if c.tie_embeddings else params["lm_head"]
    return x.astype(jnp.float32) @ head.astype(jnp.float32)


def _attention(x, layer, cos, sin, config: LlamaConfig,
               rules: ShardingRules, positions=None, mesh=None):
    c = config
    q, kk, v = qkv(x, layer, cos, sin, positions)
    q = with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"), rules)
    if c.sp_axis is not None and mesh is not None:
        from ray_tpu.ops.ring_attention import ring_attention

        out = ring_attention(q, kk, v, mesh, causal=True,
                             sp_axis=c.sp_axis,
                             heads_axis=rules.heads,
                             batch_axes=rules.batch,
                             block=c.attn_block)
    else:
        out = _flash_on_mesh(q, kk, v, c, rules)
    out = with_logical_constraint(
        out, ("batch", "seq", "heads", "head_dim"), rules)
    with part("attn_proj"):
        return jnp.einsum("bshd,hde->bse", out,
                          layer["wo"].astype(x.dtype))


def _flash_on_mesh(q, k, v, config: LlamaConfig, rules: ShardingRules):
    """Flash attention under whatever mesh is active. A Pallas kernel
    cannot be partitioned by the compiler ("Mosaic kernels cannot be
    automatically partitioned"), so on a multi-device mesh each device
    runs the kernel on its own (batch, heads) shard under ``shard_map`` —
    attention mixes neither batch rows nor heads, so no collective is
    needed. An axis the mesh does not divide (one sequence on fsdp=2:
    the benchmark's correctness check) is left whole on every device of
    that mesh axis, still under ``shard_map``: the compiler refuses the
    bare kernel on any mesh of more than one device."""
    attend = functools.partial(flash_attention, causal=True,
                               block=config.attn_block)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return attend(q, k, v)
    batch_axes, _, heads_axis, _ = (
        a if a is None or isinstance(a, tuple) else (a,)
        for a in rules.mesh_axes(("batch", "seq", "heads", "head_dim")))
    batch_axes = tuple(a for a in batch_axes or () if a in mesh.shape)
    heads_axis = tuple(a for a in heads_axis or () if a in mesh.shape)
    if q.shape[0] % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = ()
    if k.shape[2] % math.prod(mesh.shape[a] for a in heads_axis):
        heads_axis = ()
    spec = jax.sharding.PartitionSpec(batch_axes or None, None,
                                      heads_axis or None, None)
    return jax.shard_map(attend, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def make_block(config: LlamaConfig, rules: ShardingRules, cos, sin,
               positions=None, mesh=None):
    """The scanned transformer block as a reusable closure — shared by the
    full forward and pipeline-parallel stage programs
    (``models/pipeline.py``), so stage math can never drift from the
    reference forward."""
    c = config

    def block(x, layer):
        with part("attn_proj"):
            h = rmsnorm(x, layer["attn_norm"], c.norm_eps)
        h = _attention(h, layer, cos, sin, c, rules, positions, mesh)
        with part("attn_proj"):
            x = x + h
        x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
        with part("mlp"):
            x = x + mlp(rmsnorm(x, layer["mlp_norm"], c.norm_eps), layer)
        x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
        return x, None

    if c.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if c.remat_policy == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        block = jax.checkpoint(block, policy=policy)
    return block


def forward(params: Params, tokens: jax.Array, config: LlamaConfig,
            rules: Optional[ShardingRules] = None,
            positions: Optional[jax.Array] = None, mesh=None) -> jax.Array:
    """tokens (B, S) int32 → logits (B, S, vocab) float32.

    Runs the layer stack as a single scanned+rematerialized block.
    ``mesh`` is only needed for the sequence-parallel (ring attention) path.
    """
    c = config
    rules = rules or ShardingRules()
    tokens = with_logical_constraint(tokens, ("batch", "seq"), rules)
    # Gather from a replicated table view: with batch-sharded indices the
    # gather output then lands directly in the activation layout. (The table
    # is stored fsdp-sharded; XLA inserts one all-gather — cheap next to the
    # involuntary-full-remat path a sharded-table gather triggers.)
    table = with_logical_constraint(
        params["embed"], ("embed_vocab", "embed"), rules)
    with part("embed"):
        x = table.astype(c.dtype)[tokens]
    x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
    cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)

    block = make_block(c, rules, cos, sin, positions, mesh)
    x, _ = jax.lax.scan(block, x, params["layers"])

    with part("head"):
        x = rmsnorm(x, params["final_norm"], c.norm_eps)
        head = (params["embed"].T if c.tie_embeddings
                else params["lm_head"])
        # bf16 operands + f32 accumulation: full MXU rate with f32-exact
        # logits. An f32×f32 einsum here runs the MXU at a fraction of
        # bf16 peak and the head matmul is ~6% of total FLOPs —
        # measurable at the step level.
        logits = jnp.einsum("bse,ev->bsv", x, head.astype(x.dtype),
                            preferred_element_type=jnp.float32)
    return with_logical_constraint(logits, ("batch", "seq", "vocab"), rules)


def loss_fn(params: Params, batch: Dict[str, jax.Array], config: LlamaConfig,
            rules: Optional[ShardingRules] = None, mesh=None):
    """Next-token cross entropy.

    ``batch``: {"tokens": (B, S) int32, optional "mask": (B, S) 0/1 —
    positions whose *prediction* counts (mask[i] gates the loss at step i
    predicting token i+1)}.
    Returns (loss, aux dict).
    """
    tokens = batch["tokens"]
    logits = forward(params, tokens, config, rules, mesh=mesh)  # (B,S,V) f32
    return next_token_loss(logits, tokens, batch.get("mask"))


@part("loss")
def next_token_loss(logits, tokens, mask=None):
    """Cross entropy of ``logits`` (B, S, V) against the next token, and
    the metrics :func:`loss_fn` returns."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    # mask[i] gates the loss term at step i (predicting token i+1), so the
    # last position's mask value is unused.
    mask = (jnp.ones_like(targets, jnp.float32) if mask is None
            else mask[:, :-1].astype(jnp.float32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    acc = ((logits.argmax(-1) == targets) * mask).sum() / denom
    return loss, {"loss": loss, "accuracy": acc, "tokens": denom}
