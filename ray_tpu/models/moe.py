"""Mixture-of-Experts decoder (Mixtral-style), TPU-first expert parallelism.

The reference framework has no in-tree MoE — its LLM stack delegates to
vLLM (reference ``python/ray/llm/_internal/serve/deployments/llm/vllm/``),
and its EP story is torch process groups. Here expert parallelism is
GSPMD-native (the design the public MoE-on-TPU literature converged on —
GShard/Switch):

- Experts are one stacked weight tensor with a leading ``expert`` logical
  axis, sharded over the mesh's ep axes by the rule table
  (``parallel/sharding.py: expert``). No per-expert modules, no manual
  all-to-all: the dispatch einsum ``tec,th->ech`` contracts a
  token-sharded activation against a token-routed one-hot into an
  EXPERT-sharded tensor, and XLA lowers the resharding to ICI all-to-all.
- Routing is top-k softmax gating with static expert capacity
  (``capacity_factor``) so every shape is static under jit: dropped
  tokens (over capacity) pass through the residual stream untouched.
- The Switch load-balancing auxiliary loss and a router z-loss keep the
  gate from collapsing; both are collected through the layer scan.
- Attention/norms/rope reuse the Llama components, so sp (ring attention)
  and tp compose with ep via the same rule table.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.rope import rope_frequencies
from ray_tpu.parallel.sharding import ShardingRules, with_logical_constraint
from ray_tpu.util.profiling import part

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    base: llama.LlamaConfig = dataclasses.field(
        default_factory=lambda: llama.CONFIGS["tiny"])
    n_experts: int = 8
    top_k: int = 2
    # per-expert slots = ceil(top_k * tokens / n_experts * capacity_factor)
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    router_z_coef: float = 1e-3

    def capacity(self, tokens: int) -> int:
        return max(1, math.ceil(
            self.top_k * tokens * self.capacity_factor / self.n_experts))

    def num_params(self) -> int:
        c = self.base
        dense = llama.LlamaConfig.num_params(
            dataclasses.replace(c, mlp_dim=0))
        experts = self.n_experts * 3 * c.hidden * c.mlp_dim * c.n_layers
        router = c.hidden * self.n_experts * c.n_layers
        return dense + experts + router

    def active_params(self) -> int:
        """Params touched per token (what FLOPs scale with)."""
        c = self.base
        dense = llama.LlamaConfig.num_params(
            dataclasses.replace(c, mlp_dim=0))
        experts = self.top_k * 3 * c.hidden * c.mlp_dim * c.n_layers
        router = c.hidden * self.n_experts * c.n_layers
        return dense + experts + router

    def flops_per_token(self, seq: Optional[int] = None) -> float:
        c = self.base
        seq = c.max_seq if seq is None else seq
        return 6.0 * self.active_params() + 6.0 * c.n_layers * seq * c.q_dim


CONFIGS: Dict[str, MoEConfig] = {
    "debug": MoEConfig(base=llama.CONFIGS["debug"], n_experts=4, top_k=2),
    "tiny": MoEConfig(base=llama.CONFIGS["tiny"], n_experts=8, top_k=2),
    # Mixtral-8x7B-ish shapes on the Llama-8B backbone
    "8x7b": MoEConfig(base=dataclasses.replace(
        llama.CONFIGS["8b"], hidden=4096, n_layers=32, mlp_dim=14336),
        n_experts=8, top_k=2),
}


def param_logical_axes(config: MoEConfig) -> Params:
    axes = llama.param_logical_axes(config.base)
    layer_axes = dict(axes["layers"])
    for name in ("w_gate", "w_up", "w_down"):
        layer_axes.pop(name)
    layer_axes.update({
        "router": ("layers", "embed", None),  # tiny; replicated
        "we_gate": ("layers", "expert", "embed_fsdp", "mlp"),
        "we_up": ("layers", "expert", "embed_fsdp", "mlp"),
        "we_down": ("layers", "expert", "mlp", "embed_fsdp"),
    })
    axes["layers"] = layer_axes
    return axes


def init_params(config: MoEConfig, key: jax.Array) -> Params:
    c = config.base
    params = llama.init_params(c, key)
    layers = dict(params["layers"])
    for name in ("w_gate", "w_up", "w_down"):
        layers.pop(name)
    k = iter(jax.random.split(jax.random.fold_in(key, 7), 8))
    std = c.hidden ** -0.5
    out_std = std / (2 * c.n_layers) ** 0.5
    dt = c.dtype
    L, E = c.n_layers, config.n_experts

    def tn(key, shape, s):
        return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)
                * s).astype(dt)

    # the router runs in f32: tiny matmul, and gate ordering is precision-
    # sensitive (bf16 ties reshuffle top-k between devices)
    layers["router"] = tn(next(k), (L, c.hidden, E), std).astype(jnp.float32)
    layers["we_gate"] = tn(next(k), (L, E, c.hidden, c.mlp_dim), std)
    layers["we_up"] = tn(next(k), (L, E, c.hidden, c.mlp_dim), std)
    layers["we_down"] = tn(next(k), (L, E, c.mlp_dim, c.hidden), out_std)
    params["layers"] = layers
    return params


def _moe_mlp(x: jax.Array, layer: Params, config: MoEConfig,
             rules: ShardingRules) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Top-k routed expert FFN with static capacity.

    x: (B, S, H) → (B, S, H), plus router aux metrics.
    """
    c = config.base
    B, S, H = x.shape
    T = B * S
    E, K = config.n_experts, config.top_k
    C = config.capacity(T)
    # Pin the flattened token layout (the merge of batch and seq shardings):
    # without it the partitioner lets the expert-sharded layout of the
    # dispatch einsum's OUTPUT propagate backward into the per-token routing
    # tensors, then reshards their degenerate broadcast operands with
    # "involuntary full rematerialization" (seen in the 8-device dryrun).
    xt = with_logical_constraint(x.reshape(T, H), ("tokens", "embed"), rules)

    logits = jnp.einsum("th,he->te", xt.astype(jnp.float32), layer["router"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    top_w, top_idx = jax.lax.top_k(probs, K)                     # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # GShard-style slotting: earlier k-choices claim capacity first.
    dispatch = jnp.zeros((T, E, C), jnp.float32)
    combine = jnp.zeros((T, E, C), jnp.float32)
    counts = jnp.zeros((E,), jnp.int32)
    frac_dispatched = jnp.zeros((E,), jnp.float32)
    for k in range(K):  # K is a small static constant: unrolled
        mask = jax.nn.one_hot(top_idx[:, k], E, dtype=jnp.int32)  # (T, E)
        pos = counts[None, :] + jnp.cumsum(mask, axis=0) - mask   # (T, E)
        pos_t = (pos * mask).sum(-1)                              # (T,)
        kept = (pos_t < C) & (mask.sum(-1) > 0)
        counts = counts + mask.sum(0)
        slot = jax.nn.one_hot(pos_t, C, dtype=jnp.float32) \
            * kept[:, None].astype(jnp.float32)                   # (T, C)
        dispatch = dispatch + mask.astype(jnp.float32)[:, :, None] \
            * slot[:, None, :]
        # Fold the gate weight into the rank-2 slot tensor instead of
        # multiplying a (T,1,1) operand into the rank-3 product: the SPMD
        # partitioner assigns the degenerate singleton dims conflicting
        # shardings across the unrolled k-steps and falls back to
        # "involuntary full rematerialization" (seen in the 8-device dryrun).
        w_slot = slot * top_w[:, k, None]                         # (T, C)
        combine = combine + mask.astype(jnp.float32)[:, :, None] \
            * w_slot[:, None, :]
        frac_dispatched = frac_dispatched + mask.sum(0) / T

    # dispatch: token-major → expert-major; the constraint pins the expert
    # layout so XLA materializes the resharding as all-to-all over ep axes
    dispatch = with_logical_constraint(dispatch, ("tokens", None, None), rules)
    combine = with_logical_constraint(combine, ("tokens", None, None), rules)
    expert_in = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), xt)
    expert_in = with_logical_constraint(expert_in, ("expert", None, "embed"),
                                        rules)
    g = jnp.einsum("ech,ehm->ecm", expert_in, layer["we_gate"].astype(x.dtype))
    u = jnp.einsum("ech,ehm->ecm", expert_in, layer["we_up"].astype(x.dtype))
    y = jnp.einsum("ecm,emh->ech", jax.nn.silu(g) * u,
                   layer["we_down"].astype(x.dtype))
    y = with_logical_constraint(y, ("expert", None, "embed"), rules)
    out = jnp.einsum("tec,ech->th", combine.astype(x.dtype), y)

    # Switch aux loss: E * Σ_e fraction_dispatched_e · mean_prob_e — minimized
    # at uniform routing. frac counts ALL top-k assignments (pre-drop).
    mean_prob = probs.mean(0)
    aux = E * jnp.sum((frac_dispatched / K) * mean_prob)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    # fraction of (token, k) slots that fell over capacity and were dropped
    dropped = 1.0 - dispatch.sum() / (T * K)
    return out.reshape(B, S, H), {"aux": aux, "router_z": z,
                                  "dropped": dropped}


def forward(params: Params, tokens: jax.Array, config: MoEConfig,
            rules: Optional[ShardingRules] = None,
            positions: Optional[jax.Array] = None, mesh=None):
    """tokens (B, S) → (logits (B, S, V) f32, moe_metrics dict of scalars)."""
    c = config.base
    rules = rules or ShardingRules()
    tokens = with_logical_constraint(tokens, ("batch", "seq"), rules)
    table = with_logical_constraint(
        params["embed"], ("embed_vocab", "embed"), rules)
    x = table.astype(c.dtype)[tokens]
    x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
    cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)

    def block(x, layer):
        h = llama._attention(rmsnorm(x, layer["attn_norm"], c.norm_eps),
                             layer, cos, sin, c, rules, positions, mesh)
        x = x + h
        x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
        h, moe_aux = _moe_mlp(rmsnorm(x, layer["mlp_norm"], c.norm_eps),
                              layer, config, rules)
        x = x + h
        x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
        return x, moe_aux

    if c.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if c.remat_policy == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        block = jax.checkpoint(block, policy=policy)
    x, aux = jax.lax.scan(block, x, params["layers"])

    x = rmsnorm(x, params["final_norm"], c.norm_eps)
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bse,ev->bsv", x, head.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    logits = with_logical_constraint(logits, ("batch", "seq", "vocab"), rules)
    metrics = {k: v.mean() for k, v in aux.items()}  # mean over layers
    return logits, metrics


def loss_fn(params: Params, batch: Dict[str, jax.Array], config: MoEConfig,
            rules: Optional[ShardingRules] = None, mesh=None):
    """Next-token CE + router auxiliary losses. Same contract as
    ``llama.loss_fn`` so ``training.make_train_step`` takes it unchanged."""
    tokens = batch["tokens"]
    logits, moe = forward(params, tokens, config, rules, mesh=mesh)
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    mask = batch.get("mask")
    mask = (jnp.ones_like(targets, jnp.float32) if mask is None
            else mask[:, :-1].astype(jnp.float32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = (nll * mask).sum() / denom
    loss = (ce + config.aux_loss_coef * moe["aux"]
            + config.router_z_coef * moe["router_z"])
    acc = ((logits.argmax(-1) == targets) * mask).sum() / denom
    return loss, {"loss": loss, "ce": ce, "accuracy": acc, "tokens": denom,
                  "aux_loss": moe["aux"], "router_z": moe["router_z"],
                  "dropped_frac": moe["dropped"]}


# ---------------------------------------------------------------------
# The expert layer by share (serving): a chip of an expert-parallel
# deployment holds ``experts_held = (first, count)`` of the router's
# experts, routes every token over ALL of them and computes the part of
# the result that its own experts give. No capacity and no dropped
# token: the row buffer holds twice the rows the chip expects where the
# worst case (every pair routed here) would be mostly dead rows, and the
# rows past it take further passes over the same buffer, which run only
# when there are such rows. The partial sum is the layer's output; on
# one chip nothing stands in for the absent experts.

# rows of one expert a tile: from the bf16 sublanes to the MXU's 128 rows
ROW_TILES = (16, 32, 64, 128)
COUNTERS = ("expert_layer_calls", "expert_pairs", "experts_hit",
            "expert_load_max_over_mean", "expert_pairs_dropped",
            "expert_extra_passes")
# bound_serves: the dead rows' bytes from which a bounded buffer wins
_DEAD_BYTES = 32 << 20


def row_tile(T: int, top_k: int, n_experts: int, held: int) -> int:
    """Rows of one expert in a tile of the grouped products, from the
    call's static shapes alone: the smallest of ``ROW_TILES`` that holds
    MORE than ``T * top_k / n_experts`` rows, what an expert of the
    router's ``n_experts`` expects from ``T`` tokens whether the chip
    holds all of them or a share (an expert's rows scatter around that
    mean: a tile of just the mean is two tiles for half the experts);
    then halved while the padding it can add to the buffer, ``held *
    (tm - 1)`` rows, is more than the ``T * top_k`` pairs themselves
    (the XLA around the products works on every row of the buffer, and
    a chip that holds the whole expert set pads 256 last tiles). How
    many rows the buffer has at that tile is :func:`pass_rows`'s.

    The product loads each 128 x 128 tile of an expert's matrix into
    the MXU once per row tile, so 16 rows a tile pay a weight load for
    16 rows of work. That is right where an expert HAS no more: a decode
    step sends it 4-8 rows, the tile is 16 in every cell and the call is
    bound by the experts' bytes. A prefill's bucket sends it 16-85: one
    tile an expert in place of two to six (the kernel alone and the
    layer at each tile at four models' widths: PERF.md section 6,
    PR 45)."""
    pairs = T * top_k
    tm = next((t for t in ROW_TILES if t * n_experts > pairs), ROW_TILES[-1])
    while tm > ROW_TILES[0] and held * (tm - 1) > pairs:
        tm //= 2
    return tm


def pass_rows(T: int, top_k: int, n_experts: int, held: int, tm: int) -> int:
    """Rows of the buffer one pass of the held experts works on, from the
    call's static shapes alone: TWICE the pairs a chip that holds
    ``held`` of the router's ``n_experts`` expects from ``T`` tokens (a
    chip's pairs scatter around that mean far less than one expert's; at
    the train cell's 49,152 tokens the seeded router places 1.008 of
    it), never more than all ``T * top_k``, plus the padding ``held``
    last tiles can add, rounded to tiles. Where the set is held whole
    every pair is placed here and this is the worst case itself. Rows
    the sort lays past it take further passes (:func:`_passes`)."""
    pairs = T * top_k
    here = pairs if held >= n_experts else min(
        pairs, 2 * -(-pairs * held // n_experts))
    return -(-(here + held * (tm - 1)) // tm) * tm


def bound_serves(dead_rows: int, row_bytes: int) -> bool:
    """Whether a buffer of :func:`pass_rows` rows is the form a call
    takes, whose worst case has ``dead_rows`` more, each ``row_bytes``
    wide (a row of the experts' input and one of their inner width, in
    the input's dtype: what the XLA around the products gathers, casts
    and activates for every row of the buffer, live or not). A MEASURED
    rule, as ``expert_combine.kernel_serves``: from 32 MiB of dead rows.
    The layer alone on the chip gains 0.003-0.006 ms a MiB: the train
    step's 672 MiB 14.6 ms a layer, forward and backward (111.3 ->
    96.7); the share-held serve configurations' buckets of 512 to 2,048
    tokens, 40-252 MiB, 0.11-0.75 ms a layer. Under it lie their 256
    buckets and EVERY decode step (10-24 MiB: 0.06-0.15 ms of a layer's
    1.3-2.5 alone, which is not the reason to engage): a step's few
    hundred pairs scatter around the expected share as a bucket's
    thousands do not, and a skewed step must not pay a second pass,
    whose fixed parts are the combine's, the pairs' scatter and a loop's
    trip. My chip runs, PR 58: PERF.md section 6."""
    return dead_rows * row_bytes >= _DEAD_BYTES


@part("router")
def route_sigmoid_topk(x, router, bias, top_k: int, scale: float = 1.0,
                       n_group: int = 1, topk_group: int = 1,
                       norm_eps: float = 0.0):
    """``noaux_tc`` routing: scores ``s = sigmoid(x_f32 @ W_r)`` in
    float32 over every expert; the ``top_k`` largest of ``s + b`` are
    chosen (``b`` the stored correction bias, used for the choice only;
    None where the model stores none); the weights are ``s`` at the
    chosen, divided by their sum (plus ``norm_eps`` where a family
    adds one; 0 adds nothing to the program), times ``scale``. x (T, h)
    -> (idx (T, k) int32, weights (T, k) f32). A gradient reaches
    ``router`` through the gathered ``s`` and their normalisation; the
    choice (``top_k`` of ``s + b``) is integers and carries none, so
    ``bias`` gets zeros.

    Group-limited (``n_group`` > 1): the experts are ``n_group`` runs of
    equal length; a group's score is the sum of its two largest ``s +
    b``; only the ``topk_group`` best groups' experts stand for the
    choice. 1 and 1 is the choice over all, as it was."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    c = s if bias is None else s + bias.astype(jnp.float32)[None, :]
    if n_group > 1:
        T, E = c.shape
        grouped = c.reshape(T, n_group, E // n_group)
        score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, best = jax.lax.top_k(score, topk_group)            # (T, kept)
        kept = jnp.zeros((T, n_group), bool).at[
            jnp.arange(T)[:, None], best].set(True)
        c = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(T, E)
    _, idx = jax.lax.top_k(c, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    total = jnp.sum(w, axis=-1, keepdims=True)
    w = w / (total + norm_eps if norm_eps else total) * scale
    return idx.astype(jnp.int32), w


@part("router")
def route_softmax_topk(x, router, top_k: int, scale: float = 1.0):
    """Softmax routing with no capacity: ``p = softmax(x_f32 @ W_r)`` in
    float32 over every expert; the ``top_k`` largest are chosen; the
    weights are ``p`` at the chosen, divided by their sum, times
    ``scale``. No correction bias, no groups. A sibling of
    :func:`route_sigmoid_topk` and not a switch inside it: the two share
    a product and a ``top_k`` and nothing else, and the sigmoid one's
    outputs stay what they were. x (T, h) -> (idx (T, k) int32, weights
    (T, k) f32)."""
    p = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, idx = jax.lax.top_k(p, top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * scale
    return idx.astype(jnp.int32), w


def swiglu(x, gate, up, down):
    """``(silu(x gate) * (x up)) down`` in x.dtype: x (T, h), ``gate`` /
    ``up`` (h, m), ``down`` (m, h)."""
    return ((jax.nn.silu(x @ gate.astype(x.dtype)) * (x @ up.astype(x.dtype)))
            @ down.astype(x.dtype))


def relu2(x, up, down):
    """``relu(x up) ** 2 down`` in x.dtype, the square taken in float32:
    the ungated two-matrix MLP. x (T, h), ``up`` (h, m), ``down`` (m, o)."""
    a = jnp.square(jax.nn.relu(jnp.dot(
        x, up.astype(x.dtype), preferred_element_type=jnp.float32)))
    return a.astype(x.dtype) @ down.astype(x.dtype)


@part("shared_expert")
def shared_expert(x, layer: Params):
    """The expert every token passes through, whole on every chip of an
    expert-parallel deployment (the data-parallel part of the layer): a
    SwiGLU ``ws_gate`` / ``ws_up`` (h, m), ``ws_down`` (m, h), or, where
    the layer stores no ``ws_gate``, the ungated :func:`relu2` of
    ``ws_up`` and ``ws_down``. x (T, h) -> (T, h) in x.dtype."""
    if "ws_gate" not in layer:
        return relu2(x, layer["ws_up"], layer["ws_down"])
    return swiglu(x, layer["ws_gate"], layer["ws_up"], layer["ws_down"])


@jax.custom_vjp
def _rows_of_tokens(x, token_of_row, order, row_sorted, held):
    """The row buffer of a dispatch: row ``r`` is token
    ``token_of_row[r]``'s ``x``, a row that is no pair's (``T``) zeros.
    ``order``, ``row_sorted`` and ``held`` (the dispatch's own integers:
    the pairs sorted by expert, each sorted pair's row, which pairs are
    held here) are not read going forward. They make the TRANSPOSE a
    combine with unit weights: a token gathers the rows of its pairs
    placed here and sums them in float32, where ``jax.grad`` of the
    gather would scatter-add every row of the worst-case buffer."""
    del order, row_sorted, held
    return jnp.concatenate(
        [x, jnp.zeros((1, x.shape[1]), x.dtype)])[token_of_row]


def _pair_rows(order, row_sorted, held, M: int):
    """(row of each (token, expert) pair (T, k), ``M`` where it has
    none; whether the pair is placed here) from the dispatch's sort."""
    T, top_k = held.shape
    row_pair = jnp.zeros(T * top_k, jnp.int32).at[order].set(
        row_sorted.astype(jnp.int32)).reshape(T, top_k)
    return row_pair, held & (row_pair < M)


def _rows_fwd(x, token_of_row, order, row_sorted, held):
    return (_rows_of_tokens(x, token_of_row, order, row_sorted, held),
            _pair_rows(order, row_sorted, held, token_of_row.shape[0]))


def _rows_bwd(res, d_rows):
    row_pair, placed = res
    picked = jnp.where(
        placed[..., None],
        d_rows[jnp.minimum(row_pair, d_rows.shape[0] - 1)], 0)
    return (jnp.sum(picked.astype(jnp.float32), axis=1).astype(d_rows.dtype),
            None, None, None, None)


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


def _down_combine_rows(act, we_down, tile_group, n_active, order, row_sorted,
                       held, w, tm, name, experts):
    """The down product and the combine as the layer runs them:
    -> (y (T, h) float32, placed (T, k) bool, and for the backward the
    product's rows and each pair's row)."""
    from ray_tpu.ops.pallas import expert_combine, grouped_matmul as gm

    y_rows = gm.grouped_product(act, we_down, tile_group, n_active, tm=tm,
                                out_dtype=jnp.float32, name=name)
    with part("expert_combine"):
        row_pair, placed = _pair_rows(order, row_sorted, held, act.shape[0])
        # rows of tiles past n_active were never written: the XLA
        # form selects and the kernel copies placed rows only; neither
        # multiplies, or what lies there leaks through a zero weight
        y = expert_combine.combine(
            y_rows, row_pair, placed, w,
            held=we_down.shape[0], experts=experts)
    return y, placed, y_rows, row_pair


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _down_and_combine(act, we_down, tile_group, n_active, order, row_sorted,
                      held, w, tm: int, name: str, experts: int):
    """``act`` (M, m) through the held experts' ``we_down`` and each
    token's weighted sum over its pairs placed here: -> (y (T, h)
    float32, placed (T, k) bool). ONE backward for the two, so that the
    float32 gradient of the (M, h) row buffer is never built: a row
    GATHERS its token's ``dy`` in ``act``'s dtype, already times its
    pair's weight (a row is at most one placed pair's, so nothing is
    scattered into the buffer; a row that is no pair's gets zeros), and
    that is what the products' two gradients multiply
    (``grouped_matmul.product_grads``); a weight's gradient is its row's
    dot with its token's ``dy``. Rows no placed pair names are read for
    no gradient that is kept."""
    return _down_combine_rows(act, we_down, tile_group, n_active, order,
                              row_sorted, held, w, tm, name, experts)[:2]


def _down_and_combine_fwd(act, we_down, tile_group, n_active, order,
                          row_sorted, held, w, tm, name, experts):
    y, placed, y_rows, row_pair = _down_combine_rows(
        act, we_down, tile_group, n_active, order, row_sorted, held, w, tm,
        name, experts)
    return (y, placed), (act, we_down, tile_group, n_active, y_rows,
                         row_pair, placed, w)


def _down_and_combine_bwd(tm, name, experts, res, cotangents):
    from ray_tpu.ops.pallas import grouped_matmul as gm

    act, we_down, tile_group, n_active, y_rows, row_pair, placed, w = res
    dy = cotangents[0]
    T, top_k = row_pair.shape
    M = act.shape[0]
    with part("expert_combine"):
        rows = jnp.where(placed, row_pair, M).reshape(-1)
        token_of_row = jnp.full((M,), T, jnp.int32).at[rows].set(
            jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k), mode="drop")
        w_row = jnp.zeros((M,), jnp.float32).at[rows].set(
            w.astype(jnp.float32).reshape(-1), mode="drop")
        dy_rows = jnp.concatenate(
            [dy.astype(act.dtype), jnp.zeros((1, dy.shape[1]), act.dtype)]
        )[token_of_row].astype(jnp.float32)
        dot_row = jnp.sum(y_rows.astype(jnp.float32) * dy_rows, axis=1)
        dw = jnp.where(placed, dot_row[jnp.minimum(row_pair, M - 1)], 0.0)
        d_rows = (dy_rows * w_row[:, None]).astype(act.dtype)
    d_act, d_down = gm.product_grads(act, we_down, d_rows, tile_group,
                                     n_active, tm=tm, name=name)
    return (d_act, d_down, None, None, None, None, None, dw.astype(w.dtype))


_down_and_combine.defvjp(_down_and_combine_fwd, _down_and_combine_bwd)


def _pass(p, xe, w, weights, order, held, row_sorted, pend, n_tiles: int,
          tm: int, kernel_name: str, experts: int):
    """One pass of the held experts over a buffer of ``n_tiles`` tiles:
    pass ``p`` takes rows ``[p * M, (p + 1) * M)`` of the sort (``M =
    n_tiles * tm``; ``row_sorted`` is each sorted pair's row over ALL
    passes, ``pend`` the groups' padded ends) and a pair whose row lies
    in another pass is not placed in this one. ``p`` None is the one
    pass of a buffer that holds the worst case: the program it had
    before there were passes, operation for operation (every decode
    step's). -> (y (T, h) float32, the sum over the pairs placed in this
    pass; placed (T, k) bool).
    Plainly differentiable in ``xe``, ``w`` and ``weights`` (``we_gate``
    where the experts are gated, ``we_up``, ``we_down``)."""
    from ray_tpu.ops.pallas import grouped_matmul as gm

    T, top_k = held.shape
    G = weights["we_down"].shape[0]
    M = n_tiles * tm
    with part("expert_dispatch"):
        if p is not None:
            row_sorted = row_sorted - p * M
            row_sorted = jnp.where(row_sorted >= 0, row_sorted, M)
        token_of_row = jnp.full((M,), T, jnp.int32).at[row_sorted].set(
            (order // top_k).astype(jnp.int32), mode="drop")
        x_rows = _rows_of_tokens(xe, token_of_row, order, row_sorted, held)
        n_active = pend[-1] // tm
        tiles = jnp.arange(n_tiles)
        if p is not None:
            tiles = tiles + p * n_tiles
        tile = jnp.minimum(tiles, jnp.maximum(n_active - 1, 0))
        tile_group = jnp.minimum(
            jnp.searchsorted(pend, tile * tm, side="right"), G - 1)
        if p is not None:
            n_active = jnp.clip(n_active - p * n_tiles, 0, n_tiles)

    mm = functools.partial(gm.grouped_product, name=kernel_name)
    with part("expert_layer"):
        gated = "we_gate" in weights
        if gated:
            gate = mm(x_rows, weights["we_gate"], tile_group, n_active, tm=tm,
                      out_dtype=jnp.float32)
        up = mm(x_rows, weights["we_up"], tile_group, n_active, tm=tm,
                out_dtype=jnp.float32)
        act = (jax.nn.silu(gate) * up if gated
               else jnp.square(jax.nn.relu(up))).astype(xe.dtype)
        return _down_and_combine(
            act, weights["we_down"], tile_group, n_active, order, row_sorted,
            held, w, tm, kernel_name, experts)


def _trips(pend, rows: int):
    """Passes of ``rows`` rows that hold every row the sort laid out."""
    return -(-pend[-1] // rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _passes(xe, w, weights, order, held, row_sorted, pend, n_tiles: int,
            tm: int, kernel_name: str, experts: int):
    """The held experts over a BOUNDED buffer of ``n_tiles`` tiles: as
    many passes (:func:`_pass`) as the sort has rows for, ``ceil(pend[-1]
    / M)`` trips of a loop whose count is read on the device: one at the
    load the buffer was sized for, more ONLY when the router sends this
    chip more than that. -> (y (T, h) float32, the sum of the passes'
    sums; the pairs placed in them; the passes run; int32 scalars).

    A backward of its own, because ``jax.grad`` of a loop (or of a
    ``cond`` between a small and a worst-case branch) would keep every
    trip's (both branches') residuals, several arrays of the buffer's
    rows each: the residuals here are the function's inputs, and the
    backward repeats the loop, computes a pass again and transposes it
    (what ``jax.checkpoint`` around a block does to a plain layer: under
    it the forward runs once here too, its recomputation has nothing to
    keep), summing the passes' gradients in the inputs' own dtypes."""
    static = (n_tiles, tm, kernel_name, experts)

    def one(p, carry):
        y_p, placed = _pass(p, xe, w, weights, order, held, row_sorted, pend,
                            *static)
        return carry[0] + y_p, carry[1] + jnp.sum(placed, dtype=jnp.int32)

    with part("expert_layer"):
        trips = _trips(pend, n_tiles * tm)
        y = jnp.zeros((xe.shape[0], weights["we_down"].shape[2]),
                      jnp.float32)
        return (*jax.lax.fori_loop(0, trips, one, (y, jnp.int32(0))), trips)


def _passes_fwd(xe, w, weights, order, held, row_sorted, pend, *static):
    return (_passes(xe, w, weights, order, held, row_sorted, pend, *static),
            (xe, w, weights, order, held, row_sorted, pend))


def _passes_bwd(n_tiles, tm, kernel_name, experts, res, cotangents):
    xe, w, weights, *ints = res
    dy = cotangents[0]

    def one(p, grads):
        _, transpose = jax.vjp(
            lambda *a: _pass(p, *a, *ints, n_tiles, tm, kernel_name,
                             experts)[0], xe, w, weights)
        return jax.tree.map(jnp.add, grads, transpose(dy))

    with part("expert_layer"):
        grads = jax.lax.fori_loop(
            0, _trips(ints[-1], n_tiles * tm), one,
            jax.tree.map(jnp.zeros_like, (xe, w, weights)))
    return (*grads, None, None, None, None)


_passes.defvjp(_passes_fwd, _passes_bwd)


def experts_by_share(x, layer: Params, *, experts_held: Tuple[int, int],
                     top_k: int, scale: float = 1.0, valid=None,
                     kernel_name: str = "grouped_expert_matmul",
                     n_group: int = 1, topk_group: int = 1,
                     score: str = "sigmoid", x_experts=None,
                     norm_eps: float = 0.0):
    """The routed MLP of one layer on the chip that holds
    ``experts_held = (first, count)``: x (T, h) -> (y (T, h) float32,
    the partial sum over the experts held; counters float32 in the
    order of ``COUNTERS``).

    What is ROUTED and what is MULTIPLIED may be two arrays (latent
    experts): the router always reads ``x``; the experts read
    ``x_experts`` (T, l) where it is given (a projection of ``x`` to the
    experts' own width), and y is then (T, l) too, for the caller to
    project back. An expert is a SwiGLU, or, where the layer stores no
    ``we_gate``, the ungated ``relu(. we_up) ** 2 we_down`` (two grouped
    products, the square in float32).

    ``layer``: ``router`` (h, E), scored by ``score``: ``"sigmoid"``
    (:func:`route_sigmoid_topk`, with ``router_bias`` (E,) where the
    model stores one and its group limit ``n_group`` / ``topk_group``)
    or ``"softmax"`` (:func:`route_softmax_topk`); and the HELD
    experts' SwiGLU matrices ``we_gate``/``we_up`` (count, h, m),
    ``we_down`` (count, m, h). ``valid`` (T,) bool masks rows that are
    no token (a padded prompt, an idle slot): they are routed nowhere.

    The (token, expert) pairs whose expert is held are sorted by expert
    and laid out in tiles of ``tm`` rows, one expert a tile; three
    grouped products (``ops/pallas/grouped_matmul.py``) run over the
    tiles that hold rows; each token's weighted sum over the pairs
    placed here is ``ops/pallas/expert_combine.py``'s (a kernel that
    copies only those pairs' rows where a small share of the router's
    experts is held, ``kernel_serves``; XLA's gather of every pair's row
    where the set is whole).
    ``tm`` is chosen HERE, by :func:`row_tile` from ``T``, ``top_k``,
    the router's width and ``G``: 16 for a decode step, up to 128 for a
    prefill's bucket; no caller and no option names it. So are the rows
    of the buffer: the worst case, ``T * top_k + G * (tm - 1)`` rows
    rounded to tiles, where the set is held whole or the rows that
    would lie dead are too few to repay a second code path
    (:func:`bound_serves`: every decode step); else :func:`pass_rows`,
    twice the share the chip expects. Pairs the sort lays past the
    buffer are NOT dropped: they take further passes over the same rows
    (:func:`_passes`: a loop of one trip at that load), which run only
    when there are such pairs and are counted in
    ``expert_extra_passes``; ``expert_pairs_dropped`` (held less placed,
    over all passes) is 0 whatever the router does.
    ``kernel_name`` names the products' custom calls in a trace.

    ONE layer for serving and training: ``jax.grad`` goes through it.
    The products, the rows' gather, the down product with the combine
    and the loop over a bounded buffer's passes each carry a backward of
    their own (``grouped_product``, :func:`_rows_of_tokens`,
    :func:`_down_and_combine`, :func:`_passes`); the sort, the
    tiles and the counters are integers and carry none. ``norm_eps`` is
    :func:`route_sigmoid_topk`'s.
    """
    T = x.shape[0]
    xe = x if x_experts is None else x_experts
    first, G = experts_held
    E = layer["router"].shape[1]
    tm = row_tile(T, top_k, E, G)
    if score == "softmax":
        idx, w = route_softmax_topk(x, layer["router"], top_k, scale)
    elif score != "sigmoid":
        raise ValueError(f"score {score!r}: 'sigmoid' or 'softmax'")
    else:
        idx, w = route_sigmoid_topk(
            x, layer["router"], layer.get("router_bias"), top_k, scale,
            n_group, topk_group, norm_eps)
    weights = {name: layer[name] for name in ("we_gate", "we_up", "we_down")
               if name in layer}
    worst = -(-(T * top_k + G * (tm - 1)) // tm) * tm
    M = pass_rows(T, top_k, E, G, tm)
    if not bound_serves(worst - M, (xe.shape[1] + layer["we_up"].shape[2])
                        * xe.dtype.itemsize):
        M = worst
    far = -(-worst // M) * M           # a row past every pass's
    with part("expert_dispatch"):
        local = idx - first
        held = (local >= 0) & (local < G)
        if valid is not None:
            held &= valid[:, None]
        key = jnp.where(held, local, G).reshape(-1)           # (T*k,)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros(G + 1, jnp.int32).at[key].add(1)[:G]
        padded = -(-sizes // tm) * tm
        pend = jnp.cumsum(padded)
        ustart = jnp.cumsum(sizes) - sizes
        skey = key[order]
        g_of = jnp.minimum(skey, G - 1)
        row_sorted = jnp.where(
            skey < G, (pend - padded)[g_of] + jnp.arange(T * top_k)
            - ustart[g_of], far)
    passes = (xe, w, weights, order, held, row_sorted, pend)
    if M == worst:
        y, placed = _pass(None, *passes, M // tm, tm, kernel_name, E)
    else:
        y, placed, trips = _passes(*passes, M // tm, tm, kernel_name, E)
    with part("expert_dispatch"):       # the step's counters: group sizes
        pairs = jnp.sum(sizes).astype(jnp.float32)
        counters = jnp.stack([
            jnp.float32(1.0), pairs,
            jnp.sum(sizes > 0).astype(jnp.float32),
            jnp.max(sizes) * G / jnp.maximum(pairs, 1.0),
            jnp.sum(held).astype(jnp.float32)
            - jnp.sum(placed).astype(jnp.float32),
            jnp.float32(0.0) if M == worst
            else jnp.maximum(trips - 1, 0).astype(jnp.float32)])
    return y, counters
