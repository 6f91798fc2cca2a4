"""The adapter of the ``lfm2_moe`` block (``"architecture": "lfm2"``) on
the TRAIN path: one mixer a layer by ``layer_types`` (a gated short
convolution, or grouped-query attention with per-head q/k norms before
the rotary), a SwiGLU that is dense in the leading ``num_dense_layers``
layers and routed after them (sigmoid scores, a choice bias, top-k, no
capacity), a tied head. The program's side is ``ray_tpu.models.lfm2``
over ``moe.experts_by_share``; ISSUE 57 writes the equations out and
``benchmark/reference/lfm2.py`` repeats them.

The keys are the published ones (``lfm2_moe``'s ``config.json``). A file
that runs a CUT of the model states it with four keys beside them:
``num_hidden_layers`` layers starting at the published layer
``layer_first`` (``layer_types`` stands whole; the run reads
``layer_types[layer_first : layer_first + num_hidden_layers]``, the
first ``num_dense_layers`` of them dense), ``num_experts`` experts HELD
of a router ``router_width`` wide from ``experts_first`` on, and
``vocab_size`` rows of the vocabulary. Without the three extra keys the
file is the uncut model.

Importing it imports no jax. No cell serves this block: a convolution's
state a slot has no builder in the program (``NO_SERVE``).
"""

from __future__ import annotations

KEYS = (
    "conv_L_cache", "conv_bias", "hidden_size", "intermediate_size",
    "layer_types", "max_position_embeddings", "moe_intermediate_size",
    "norm_eps", "norm_topk_prob", "num_attention_heads", "num_dense_layers",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "rope_parameters", "routed_scaling_factor",
    "use_expert_bias", "vocab_size")
PUBLISHED_PARAMS = 23.84e9      # ISSUE 57's count of the published keys
NO_SERVE = ("the lfm2 block has no serving path: no serve cell runs it (a "
            "short convolution's per-slot state has no builder in "
            "ray_tpu/models/lfm2.py and no kind in the KV state manager; "
            "PERF.md section 7)")


def check_config(spec: dict) -> None:
    """The program's side of the block, the keys it needs, what of the
    family this block does not run, and the count of the published keys:
    a checkout whose program lacks the module (a commit from before the
    block was added) exits here, in the driver, before any process is
    started."""
    import os

    name = spec.get("name")
    program = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "ray_tpu", "models", "lfm2.py")
    if not os.path.isfile(program):
        raise SystemExit(f"config {name!r}: this checkout's program cannot "
                         f"run the lfm2 block: no file {program}")
    missing = sorted(k for k in KEYS if k not in spec)
    if missing:
        raise SystemExit(f"config {name!r}: the lfm2 block needs the keys "
                         f"{missing}")
    if (spec["conv_bias"] or not spec["norm_topk_prob"]
            or not spec["use_expert_bias"]
            or spec["rope_parameters"].get("rope_type") != "default"
            or spec["hidden_size"] % spec["num_attention_heads"]):
        raise SystemExit(
            f"config {name!r}: the lfm2 block has no convolution bias, "
            "normalises the chosen scores, stores a choice bias, rotates "
            "unscaled and splits the width evenly over its heads")
    first, L = spec.get("layer_first", 0), spec["num_hidden_layers"]
    if first + L > len(spec["layer_types"]) or not (
            0 <= spec.get("experts_first", 0)
            and spec.get("experts_first", 0) + spec["num_experts"]
            <= router_width(spec)):
        raise SystemExit(
            f"config {name!r}: layers {first}..{first + L} of "
            f"{len(spec['layer_types'])} layer_types, experts "
            f"{spec.get('experts_first', 0)}+{spec['num_experts']} of "
            f"{router_width(spec)}: the cut lies outside the model")
    if "published" in spec:
        whole = num_params(spec["published"])
        if abs(whole / PUBLISHED_PARAMS - 1.0) > 1e-2:
            raise SystemExit(f"config {name!r}: the published keys count "
                             f"{whole / 1e9:.3f} B parameters, not 23.84 B")


# ------------------------------------------------------------------ counts
def router_width(spec: dict) -> int:
    return spec.get("router_width", spec["num_experts"])


def layer_kinds(spec: dict, layers: int | None = None) -> list:
    """(mixer, routed) of each layer run."""
    first = spec.get("layer_first", 0)
    n = spec["num_hidden_layers"] if layers is None else layers
    return [(kind, i >= spec["num_dense_layers"])
            for i, kind in enumerate(spec["layer_types"][first:first + n])]


def head_dim(spec: dict) -> int:
    return spec["hidden_size"] // spec["num_attention_heads"]


def _mixer_params(spec: dict, kind: str) -> int:
    h = spec["hidden_size"]
    if kind == "conv":
        return 4 * h * h
    kv = spec["num_key_value_heads"] * head_dim(spec)
    return 2 * h * h + 2 * h * kv


def matrix_params(spec: dict, layers: int | None = None) -> dict:
    """Parameters that take part in a matrix multiply, by group, of what
    this file HOLDS: the mixers, the dense SwiGLUs, the routers, the
    experts held (``experts``: all of them; ``experts_a_token``: what
    one token is EXPECTED to touch, ``num_experts_per_tok * held /
    router_width`` experts a routed layer) and the head (the tied table
    as the head multiplies it)."""
    h = spec["hidden_size"]
    kinds = layer_kinds(spec, layers)
    routed = sum(r for _, r in kinds)
    one = 3 * h * spec["moe_intermediate_size"]
    return {
        "mixers": sum(_mixer_params(spec, kind) for kind, _ in kinds),
        "dense": (len(kinds) - routed) * 3 * h * spec["intermediate_size"],
        "routers": routed * h * router_width(spec),
        "experts": routed * spec["num_experts"] * one,
        "experts_a_token": routed * one * spec["num_experts_per_tok"]
        * spec["num_experts"] / router_width(spec),
        "head": h * spec["vocab_size"]}


def num_params(spec: dict, layers: int | None = None) -> int:
    """All stored parameters of what this file holds: the tied table
    once, every layer's two norms (an attention layer's q/k norms, a
    convolution's taps, a routed layer's choice bias), the final norm."""
    h = spec["hidden_size"]
    kinds = layer_kinds(spec, layers)
    mp = matrix_params(spec, layers)
    small = sum(2 * h
                + (spec["conv_L_cache"] * h if kind == "conv"
                   else 2 * head_dim(spec))
                + (router_width(spec) if routed else 0)
                for kind, routed in kinds)
    return (mp["head"] + mp["mixers"] + mp["dense"] + mp["routers"]
            + mp["experts"] + small + h)


def train_flops_per_token(spec: dict, seq: int) -> float:
    """Operations the forward and backward passes REQUIRE for one
    trained token of what is HELD: 6 for every parameter a token
    multiplies (2 forward, 4 backward): the mixers, the dense SwiGLU,
    the routers, the head, and of the experts the EXPECTATION,
    ``num_experts_per_tok * held / router_width`` experts a routed layer
    (4 x 16 / 64 = 1 pair a token in the cell's cut: the router sends
    the other three to chips that are not here); none for the embedding
    gather, the convolution's taps (3 multiply-adds a channel), the
    padding of a tile or recomputation; plus causal attention in the
    attention layers, 6 * S * d a layer (d = heads * head size, 32 x 64)
    as the dense adapter counts it."""
    mp = matrix_params(spec)
    attn = sum(kind != "conv" for kind, _ in layer_kinds(spec))
    return (6.0 * (mp["mixers"] + mp["dense"] + mp["routers"]
                   + mp["experts_a_token"] + mp["head"])
            + 6.0 * attn * seq * spec["hidden_size"])


def flash_flops(spec: dict, batch: int, seq: int) -> dict:
    """ONE call of each causal flash kernel at this block's heads (32
    query heads of 64 over 8): the dense adapter's count."""
    tri = batch * spec["num_attention_heads"] * seq * seq * head_dim(spec)
    return {"fwd": 2 * tri, "bwd_dq": 3 * tri, "bwd_dkv": 4 * tri}


def kv_bytes_per_token(spec: dict) -> int:
    raise SystemExit(NO_SERVE)


_FLASH = {"flash_attention_fwd": "fwd", "flash_attention_dq": "bwd_dq",
          "flash_attention_dkv": "bwd_dkv"}
_GROUPED = ("grouped_expert_matmul", "grouped_expert_matmul_dw")


def kernel_counts(spec: dict, kernel: str, **sizes) -> dict:
    """Operations of ONE call of a kernel of the train step. The grouped
    products, forward (and the rows' gradient, the same kernel under the
    same name) and ``dw`` alike: ``2 * rows * hidden *
    moe_intermediate_size`` at the rows EXPECTED here, ``batch * seq *
    num_experts_per_tok * held / router_width`` (each of an expert's
    three matrices is hidden x 1536 one way or the other; the rows a
    tile pads and the tiles the worst-case buffer leaves empty are no
    required work). ``sizes``: ``batch``, ``seq``."""
    if kernel in _FLASH:
        return {"flops": flash_flops(spec, sizes["batch"],
                                     sizes["seq"])[_FLASH[kernel]]}
    if kernel in _GROUPED:
        rows = (sizes["batch"] * sizes["seq"] * spec["num_experts_per_tok"]
                * spec["num_experts"] / router_width(spec))
        return {"flops": 2.0 * rows * spec["hidden_size"]
                * spec["moe_intermediate_size"]}
    raise KeyError(f"lfm2 counts no kernel named {kernel!r}")


# ----------------------------------------------------------------- program
def program_config(spec: dict):
    from ray_tpu.models import lfm2

    first = spec.get("layer_first", 0)
    return lfm2.Lfm2Config(
        vocab_size=spec["vocab_size"], hidden=spec["hidden_size"],
        layer_types=tuple(
            spec["layer_types"][first:first + spec["num_hidden_layers"]]),
        num_dense_layers=spec["num_dense_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"], head_dim=head_dim(spec),
        conv_taps=spec["conv_L_cache"], mlp_dim=spec["intermediate_size"],
        n_experts=router_width(spec), top_k=spec["num_experts_per_tok"],
        moe_dim=spec["moe_intermediate_size"],
        experts_held=(spec.get("experts_first", 0), spec["num_experts"]),
        route_scale=float(spec["routed_scaling_factor"]),
        rope_theta=float(spec["rope_parameters"]["rope_theta"]),
        norm_eps=spec["norm_eps"])


# ----------------------------------------------------------------- weights
def weight_shapes(spec: dict) -> dict:
    """The tree ``lfm2.init_params`` gives: ``embed``, ``layers`` a LIST
    of one dict a layer, ``final_norm``; a norm's stored weight ``w``
    scales by ``1 + w``."""
    from ray_tpu.models import lfm2

    return lfm2.param_shapes(program_config(spec))


def weight_stds(spec: dict) -> tuple:
    """Normal draws at ``hidden ** -0.5``; every projection back into
    the stream (a convolution's and attention's out, the dense and an
    expert's down) scaled down by ``sqrt(2 L)``; norm weights, the q/k
    norms' too, at 0.1 so that a dropped ``1 + w`` shows; the taps at
    ``taps ** -0.5`` (of order one: the convolution keeps the size of
    what it filters); the router at ``hidden ** -0.5`` and its choice
    bias at 0.01, as ``mimo_v2.py``'s adapter draws the same router: the
    published bias is what a load balancer leaves, small shifts, and a
    draw at 0.1 made one expert's load 6.4 times the mean there (PERF.md,
    PR 27)."""
    std = spec["hidden_size"] ** -0.5
    out_std = std / (2 * spec["num_hidden_layers"]) ** 0.5
    return std, {"op_norm": 0.1, "ffn_norm": 0.1, "final_norm": 0.1,
                 "q_norm": 0.1, "k_norm": 0.1,
                 "conv_k": spec["conv_L_cache"] ** -0.5,
                 "w_out": out_std, "wo": out_std, "w_down": out_std,
                 "we_down": out_std, "router_bias": 0.01}


# ------------------------------------------------------------------ serving
def engine_kwargs(spec: dict, deployment: dict) -> dict:
    raise SystemExit(NO_SERVE)


def serve_program_logits(params, spec: dict, tokens, deployment: dict, *,
                         prefill: int):
    raise SystemExit(NO_SERVE)


def lower_serve_programs(spec: dict, deployment: dict, device):
    raise SystemExit(NO_SERVE)


# ------------------------------------------------- the check's program side
def train_program_loss_and_grads(params, spec: dict, tokens, rules=None):
    """The program's loss and gradients on one sequence through the code
    the train step differentiates (``lfm2.hidden``: the remat blocks,
    the flash kernels, ``experts_by_share`` and its backward;
    ``lfm2.logits_of``; ``llama.next_token_loss``: what ``lfm2.loss_fn``
    composes). The head is handed its table as an argument of its own,
    so that ``lm_head`` is the tied table's gradient through the HEAD
    alone, as the reference's tail has it (the gather's part runs
    through every layer). Returns the loss and the gradients of the last
    block, the final norm and the head."""
    import jax
    from ray_tpu.models import llama, lfm2

    cfg = program_config(spec)

    def f(p, head, toks):
        x, _ = lfm2.hidden(p, toks[None, :], cfg, rules)
        logits = lfm2.logits_of(x, p["final_norm"], head.T, cfg)
        return llama.next_token_loss(logits, toks[None, :])[0]

    def tail_of(p, toks):
        loss, (g, g_head) = jax.value_and_grad(f, argnums=(0, 1))(
            p, p["embed"].T, toks)
        return loss, {"layer": g["layers"][-1],
                      "final_norm": g["final_norm"], "lm_head": g_head}

    return jax.jit(tail_of)(params, tokens)


# ------------------------------------------------- programs from shapes alone
def train_setup(spec: dict, job: dict, mesh):
    """(state shapes with shardings, the jitted step, the rules,
    ``key -> train state`` sharded from birth): ``make_train_step``
    around ``lfm2.loss_fn``; the optimizer is the traffic mix's
    ``OptimizerConfig`` for every parameter and nothing for the choice
    bias (``lfm2.frozen_buffers``)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import lfm2
    from ray_tpu.models.training import (OptimizerConfig, TrainState,
                                         init_train_state, make_train_step,
                                         state_shardings)
    from ray_tpu.parallel.sharding import FSDP_TP_RULES

    from benchmark import weights

    cfg = program_config(spec)
    rules = FSDP_TP_RULES
    axes = lfm2.param_logical_axes(cfg)
    opt = lfm2.frozen_buffers(
        OptimizerConfig(**job.get("optimizer", {"warmup_steps": 1})).make(),
        lfm2.param_shapes(cfg))
    init = weights.init_fn(spec)

    def build(key):
        params = init(key)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt.init(params))

    shape = jax.eval_shape(build, jax.eval_shape(lambda: jax.random.key(0)))
    shardings = state_shardings(shape, axes, mesh, rules)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shape, shardings)
    step = make_train_step(lambda p, b: lfm2.loss_fn(p, b, cfg, rules),
                           opt, mesh, rules)

    def init_state(key):
        return init_train_state(init, axes, opt, mesh, rules, key)[0]

    return state, step, rules, init_state
