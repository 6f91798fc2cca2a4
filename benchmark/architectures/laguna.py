"""The adapter of the ``laguna`` language model's block
(``"architecture": "laguna"``): full and sliding-window attention layers
mixed by ``layer_types`` whose QUERY heads differ by layer
(``num_attention_heads_per_layer``) over one count of KV heads, a
per-head sigmoid gate on the attention output, rotary by layer type
(YaRN on half a head in the full layers), a dense SwiGLU in the layers
``mlp_layer_types`` calls ``dense`` and in the others a shared expert
beside a softmax-routed expert MLP with no capacity. The program's side
is ``ray_tpu.models.laguna`` on the paged serving path; the reference is
``benchmark/reference/laguna.py``.

The configuration the benchmark runs holds EVERY expert (``num_experts``
= the router's width), so its layers' results are whole. The counts
below are of what a configuration's keys say, so the same functions give
the uncut model from its ``published`` keys; a share (``num_experts``
the experts held from ``experts_first`` on, ``router_width`` the
router's) is taken by the tests that tie a share to the model.

Importing it imports no jax. The contract is the table in
``benchmark/README.md``.
"""

from __future__ import annotations

import math

KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_key_value_heads", "head_dim", "max_position_embeddings",
    "rms_norm_eps", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "tie_word_embeddings", "gating", "sliding_window", "rope_parameters",
    "layer_types", "mlp_layer_types", "moe_routed_scaling_factor",
    "moe_apply_router_weight_on_input", "num_attention_heads_per_layer")
LAYER_TYPES = {"full_attention": "full", "sliding_attention": "window"}
PUBLISHED_PARAMS = 33.44e9          # the family states 33.4B-A3B
NO_TRAIN = ("the laguna block has no train path: no train cell runs it (at "
            "16 bytes a parameter it fits only as one of 8 chips that share "
            "each layer, on a path with no window in its flash kernel and "
            "dropped tokens in its expert layer)")


def yarn_table_factor(rope: dict) -> float:
    """What ``ray_tpu.ops.rope.YarnScaling(mscale=1, mscale_all_dim=0)``
    multiplies cos and sin by: ``0.1 ln(factor) + 1``."""
    return 0.1 * math.log(float(rope["factor"])) + 1.0


def check_config(spec: dict) -> None:
    """The keys this block needs, and the program's side of it: a
    checkout whose program lacks the module (a commit from before the
    block was added) exits here, in the driver, before any process is
    started."""
    import os

    name = spec.get("name")
    program = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "ray_tpu", "models", "laguna.py")
    if not os.path.isfile(program):
        raise SystemExit(f"config {name!r}: this checkout's program cannot "
                         f"run the laguna block: no file {program}")
    missing = sorted(k for k in KEYS if k not in spec)
    if missing:
        raise SystemExit(f"config {name!r}: the laguna block needs the "
                         f"keys {missing}")
    n = spec["num_hidden_layers"]
    rope = spec["rope_parameters"]
    if (spec["tie_word_embeddings"] or not spec["gating"]
            or spec["moe_apply_router_weight_on_input"]
            or min(len(spec[k]) for k in (
                "layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer")) < n
            or set(spec["layer_types"]) - set(LAYER_TYPES)
            or rope["full_attention"]["rope_type"] != "yarn"
            or rope["sliding_attention"]["rope_type"] != "default"):
        raise SystemExit(
            f"config {name!r}: the laguna block has an untied head, a gate "
            "on every head's attention output, router weights on the "
            "experts' outputs, a type, an MLP type and a head count for "
            "every layer, YaRN in the full layers and plain rotary in the "
            "window layers")
    stated = rope["full_attention"].get("attention_factor")
    if stated is not None and abs(
            stated - yarn_table_factor(rope["full_attention"])) > 1e-9:
        raise SystemExit(
            f"config {name!r}: attention_factor {stated!r} is not "
            "0.1 ln(factor) + 1, which is what the program's YarnScaling "
            "puts on its tables")
    if "published" in spec:
        uncut = num_params(spec["published"])
        if abs(uncut / PUBLISHED_PARAMS - 1.0) > 1e-3:
            raise SystemExit(f"config {name!r}: the published keys count "
                             f"{uncut / 1e9:.3f} B parameters, not 33.44 B")


# ------------------------------------------------------------------ counts
def _layers(spec, layers):
    return spec["num_hidden_layers"] if layers is None else layers


def router_width(spec: dict) -> int:
    return spec.get("router_width", spec["num_experts"])


def layer_kinds(spec: dict, layers: int | None = None) -> list:
    """"full" or "window" for each layer that is run."""
    return [LAYER_TYPES[t]
            for t in spec["layer_types"][:_layers(spec, layers)]]


def _routed_layers(spec, layers=None) -> int:
    return sum(t == "sparse"
               for t in spec["mlp_layer_types"][:_layers(spec, layers)])


def heads_of(spec: dict, kind: str) -> int:
    """Query heads of a layer of that kind (every layer of a kind has
    the same count in the published lists)."""
    found = {H for H, k in zip(spec["num_attention_heads_per_layer"],
                               layer_kinds(spec)) if k == kind}
    if len(found) != 1:
        raise SystemExit(f"{kind} layers with query heads {sorted(found)}")
    return found.pop()


def attention_params(spec: dict, heads: int) -> int:
    """Of one layer with that many query heads: q, k, v, the gate's
    (hidden, heads) and the output projection."""
    h, D, KV = spec["hidden_size"], spec["head_dim"], \
        spec["num_key_value_heads"]
    return h * heads * D + 2 * h * KV * D + h * heads + heads * D * h


def expert_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["moe_intermediate_size"]


def matrix_params(spec: dict, layers: int | None = None) -> dict:
    """Stored parameters that take part in a matrix multiply, by group
    (of the routed experts: those the configuration holds)."""
    h = spec["hidden_size"]
    n, routed = _layers(spec, layers), _routed_layers(spec, layers)
    return {
        "attention": sum(attention_params(spec, H) for H in
                         spec["num_attention_heads_per_layer"][:n]),
        "dense_mlp": (n - routed) * 3 * h * spec["intermediate_size"],
        "shared_experts": routed * 3 * h
        * spec["shared_expert_intermediate_size"],
        "experts": routed * spec["num_experts"] * expert_params(spec),
        "router": routed * h * router_width(spec),
        "head": h * spec["vocab_size"]}


def num_params(spec: dict, layers: int | None = None) -> int:
    """All stored parameters: embedding, head, the matrices above, two
    norms a layer and the final one."""
    h = spec["hidden_size"]
    return (spec["vocab_size"] * h + sum(matrix_params(spec, layers).values())
            + _layers(spec, layers) * 2 * h + h)


def active_params(spec: dict, layers: int | None = None) -> int:
    """Parameters one token passes through: all but the experts it is
    not routed to."""
    idle = spec["num_experts"] - spec["num_experts_per_tok"]
    return (num_params(spec, layers)
            - _routed_layers(spec, layers) * idle * expert_params(spec))


def train_flops_per_token(spec: dict, seq: int) -> float:
    raise SystemExit(NO_TRAIN)


def kv_bytes_per_token(spec: dict, kind: str = "full") -> int:
    """Bytes of keys and values one cached token takes over ALL the
    layers of that kind that are run (bf16, keys and values ``head_dim``
    wide for every KV head): what a token costs the kind's pool."""
    return (layer_kinds(spec).count(kind) * spec["num_key_value_heads"]
            * 2 * spec["head_dim"] * 2)


def blocks_in_window(spec: dict, block_size: int) -> int:
    """Blocks that the keys ``(p - window, p]`` can touch, for any p."""
    return (spec["sliding_window"] - 2) // block_size + 2


def window_pool_bytes_per_slot(spec: dict, block_size: int) -> int:
    """What the window layers' pool holds for one slot: as many blocks
    as a window can touch, in every window layer."""
    return (blocks_in_window(spec, block_size) * block_size
            * kv_bytes_per_token(spec, "window"))


def paged_hybrid_decode_bytes(spec: dict, kind: str, live_tokens: float,
                              slots: int, block_size: int = 64) -> float:
    """Bytes ONE call (one layer) of the paged decode kernel of that
    kind has to move: every live token's keys and values once (for a
    window layer: what the window leaves live, and never more than the
    blocks of a window a slot), the queries in and the outputs out."""
    row = spec["num_key_value_heads"] * 2 * spec["head_dim"] * 2
    if kind == "window":
        live_tokens = min(live_tokens, slots * block_size
                          * blocks_in_window(spec, block_size))
    return (live_tokens * row
            + slots * heads_of(spec, kind) * 2 * spec["head_dim"] * 2)


def grouped_expert_matmul_bytes(spec: dict, experts_hit: float,
                                pairs: float, layer_calls: float) -> float:
    """Bytes ONE grouped product of an expert layer has to move, as the
    mean over the ``layer_calls`` the counters cover: the (hidden x
    expert width) matrix of each expert HIT (one that has a token; with
    the whole set held, how many are hit follows the batch and the
    routing), and a row in and a row out for each (token, expert) pair,
    in bf16. (The gate and up products write float32, which this leaves
    out: it errs low.)"""
    h, m = spec["hidden_size"], spec["moe_intermediate_size"]
    calls = max(layer_calls, 1.0)
    return (experts_hit / calls) * h * m * 2 + (pairs / calls) * (h + m) * 2


def kernel_counts(spec: dict, kernel: str, **sizes) -> dict:
    """Bytes of ONE call of the kernel whose custom call carries this
    instruction name. ``sizes``: ``live_tokens``, ``slots`` for the paged
    kernels; ``experts_hit``, ``pairs``, ``layer_calls`` (the engine's
    ``model_counters``) for the grouped product of a decode step, and
    the same with ``prefill_`` before them (``model_counters_prefill``)
    for a prefill's, whose custom calls carry a name of their own."""
    if kernel in ("paged_hybrid_decode_full", "paged_hybrid_decode_window"):
        return {"bytes": paged_hybrid_decode_bytes(
            spec, kernel.rsplit("_", 1)[1], sizes["live_tokens"],
            sizes["slots"])}
    if kernel == "grouped_expert_matmul":           # a decode step's
        return {"bytes": grouped_expert_matmul_bytes(
            spec, sizes["experts_hit"], sizes["pairs"],
            sizes["layer_calls"])}
    if kernel == "grouped_expert_matmul_prefill":
        return {"bytes": grouped_expert_matmul_bytes(
            spec, sizes["prefill_experts_hit"], sizes["prefill_pairs"],
            sizes["prefill_layer_calls"])}
    raise KeyError(f"laguna counts no kernel named {kernel!r}")


# ----------------------------------------------------------------- program
def program_kwargs(spec: dict) -> dict:
    n, D = spec["num_hidden_layers"], spec["head_dim"]
    full = spec["rope_parameters"]["full_attention"]
    swa = spec["rope_parameters"]["sliding_attention"]
    return dict(
        vocab_size=spec["vocab_size"], hidden=spec["hidden_size"],
        n_layers=n, heads=tuple(spec["num_attention_heads_per_layer"][:n]),
        n_kv_heads=spec["num_key_value_heads"], head_dim=D,
        rotary_dim=int(full["partial_rotary_factor"] * D),
        swa_rotary_dim=int(swa["partial_rotary_factor"] * D),
        rope_theta=float(full["rope_theta"]),
        swa_rope_theta=float(swa["rope_theta"]),
        # the published attention_factor is the TABLES' factor
        # (check_config holds it to 0.1 ln(factor) + 1): mscale 1 over
        # mscale_all_dim 0, and the softmax scale keeps head_dim ** -0.5
        yarn=dict(factor=float(full["factor"]),
                  original_max_seq=full["original_max_position_embeddings"],
                  beta_fast=float(full["beta_fast"]),
                  beta_slow=float(full["beta_slow"]),
                  mscale=1.0, mscale_all_dim=0.0),
        window=spec["sliding_window"],
        layer_kinds=tuple(int(k == "window") for k in layer_kinds(spec)),
        moe_layers=tuple(int(t == "sparse")
                         for t in spec["mlp_layer_types"][:n]),
        mlp_dim=spec["intermediate_size"],
        expert_dim=spec["moe_intermediate_size"],
        shared_dim=spec["shared_expert_intermediate_size"],
        n_experts=router_width(spec), top_k=spec["num_experts_per_tok"],
        experts_held=(spec.get("experts_first", 0), spec["num_experts"]),
        routed_scale=float(spec["moe_routed_scaling_factor"]),
        norm_eps=spec["rms_norm_eps"],
        max_seq=spec["max_position_embeddings"])


def program_config(spec: dict):
    from ray_tpu.models import laguna
    from ray_tpu.ops.rope import YarnScaling

    kw = program_kwargs(spec)
    return laguna.LagunaConfig(**dict(kw, yarn=YarnScaling(**kw["yarn"])))


def engine_kwargs(spec: dict, deployment: dict) -> dict:
    """Keyword arguments of ``LLMEngine`` but the weights.
    ``kv_pool_tokens`` sizes the full layers' pool; the window layers'
    pool follows from the slots and the window (nine blocks a slot at a
    window of 512 and block 64)."""
    return dict(config=program_config(spec), seed=0,
                num_slots=deployment["num_slots"],
                max_seq=deployment["max_seq"], kv_cache="paged",
                kv_pool_tokens=deployment["kv_pool_tokens"],
                kv_block_size=deployment["kv_block_size"],
                prefix_cache="off")


# ----------------------------------------------------------------- weights
def weight_shapes(spec: dict) -> dict:
    """The tree the program's builders take: ``layers`` a LIST, one dict
    a layer (they are not alike); a norm's stored ``w`` scales by
    ``1 + w``; a routed layer holds its router, the shared expert and
    the held experts' matrices stacked on a leading axis."""
    h, D, KV = spec["hidden_size"], spec["head_dim"], \
        spec["num_key_value_heads"]
    m, G = spec["moe_intermediate_size"], spec["num_experts"]
    ms = spec["shared_expert_intermediate_size"]
    layers = []
    for l in range(spec["num_hidden_layers"]):
        H = spec["num_attention_heads_per_layer"][l]
        layer = {"attn_norm": (h,), "wq": (h, H, D), "wk": (h, KV, D),
                 "wv": (h, KV, D), "w_out_gate": (h, H), "wo": (H, D, h),
                 "mlp_norm": (h,)}
        if spec["mlp_layer_types"][l] == "sparse":
            layer.update(router=(h, router_width(spec)),
                         ws_gate=(h, ms), ws_up=(h, ms), ws_down=(ms, h),
                         we_gate=(G, h, m), we_up=(G, h, m),
                         we_down=(G, m, h))
        else:
            layer.update(w_gate=(h, spec["intermediate_size"]),
                         w_up=(h, spec["intermediate_size"]),
                         w_down=(spec["intermediate_size"], h))
        layers.append(layer)
    return {"embed": (spec["vocab_size"], h), "layers": layers,
            "final_norm": (h,), "lm_head": (h, spec["vocab_size"])}


def weight_stds(spec: dict) -> tuple:
    """Normal draws at ``hidden ** -0.5`` (the gate's projection among
    them: its sigmoid then sees numbers of order one); every projection
    back into the residual stream (attention out, dense down, the shared
    and the routed experts' down) scaled down by ``sqrt(2 L)`` so that
    activations stay of order one through the depth; a routed expert's
    down projection drawn ``moe_routed_scaling_factor`` times smaller
    still, because the routed sum is multiplied by that factor before it
    joins the stream (a trained model's experts have learned their
    output under the factor; PERF.md, PR 31 and PR 27: drawn without it
    one flipped router choice moved a row's logits past what the int8
    control reads); norm weights at 0.1 so that a dropped ``1 + w``
    shows."""
    std = spec["hidden_size"] ** -0.5
    out_std = std / (2 * spec["num_hidden_layers"]) ** 0.5
    return std, {"attn_norm": 0.1, "mlp_norm": 0.1, "final_norm": 0.1,
                 "wo": out_std, "w_down": out_std, "ws_down": out_std,
                 "we_down": out_std / float(
                     spec["moe_routed_scaling_factor"])}


# ------------------------------------------------- the check's program side
def _programs(params, spec: dict, deployment: dict, pool_tokens: int,
              window_slots: int):
    """The builders at the deployment's geometry, over a full pool of
    ``pool_tokens`` and a window pool for ``window_slots`` slots."""
    from ray_tpu.models import laguna

    cfg = program_config(spec)
    page = laguna.pages(cfg, num_slots=window_slots,
                        max_seq=deployment["max_seq"],
                        block_size=deployment["kv_block_size"],
                        pool_tokens=pool_tokens)
    return (cfg, page, laguna.make_prefill(params, cfg, page),
            laguna.make_decode_step(params, cfg, page))


def serve_program_logits(params, spec: dict, tokens, deployment: dict, *,
                         prefill: int):
    """Prefill of the first ``prefill`` tokens, then one teacher-forced
    decode step for each token after them through scratch pools of both
    kinds, with the builders the engine uses at the engine's slot count,
    ``max_seq`` and block size, and the engine's order of work on the
    tables (trim what the window has passed, then grow). The scratch
    pools are SMALL, so the check does not double the cache: the full
    pool the blocks this one sequence needs and one more, the window
    pool one slot's blocks (the engine's is 0.91 GB at the cell's
    figures). -> (1 + steps, vocab) float32."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import laguna
    from ray_tpu.models.paged_cache import pad_to_block_bucket

    num_slots, bs = deployment["num_slots"], deployment["kv_block_size"]
    toks = np.asarray(tokens)
    total = len(toks)
    cfg, page, prefill_fn, decode = _programs(
        params, spec, deployment, bs * (1 + -(-(total + 1) // bs)), 1)
    alloc = laguna.make_manager(cfg, page, num_slots)
    cache = laguna.init_cache(cfg, page, num_slots)
    slot = num_slots - 1                  # not the first: indexing shows
    if not alloc.ensure(slot, prefill + 1):
        raise RuntimeError("the scratch pools are too small for the check")
    P = pad_to_block_bucket(prefill, bs)
    padded = np.zeros((1, P), np.int32)
    padded[0, :prefill] = toks[:prefill]
    cache, lg = prefill_fn(cache, alloc.table_rows(slot),
                           jnp.asarray(padded), prefill, slot)
    rows = [np.asarray(lg, np.float32).reshape(-1)]
    active = np.zeros(num_slots, bool)
    active[slot] = True
    for i in range(total - prefill):
        alloc.trim(slot, prefill + i + 1)
        if not alloc.ensure(slot, prefill + i + 1):
            raise RuntimeError("the scratch pools are too small")
        last = np.zeros(num_slots, np.int32)
        last[slot] = toks[prefill + i]
        cache, lg = decode(cache, alloc.device_tables(), jnp.asarray(last),
                           jnp.asarray(active))
        rows.append(np.asarray(lg, np.float32)[slot])
    return np.stack(rows)


def train_program_loss_and_grads(params, spec: dict, tokens, rules=None):
    raise SystemExit(NO_TRAIN)


# ------------------------------------------------- programs from shapes alone
def lower_serve_programs(spec: dict, deployment: dict, device):
    """(decode step, bucket -> prefill) lowered for one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.models import laguna

    from benchmark import weights
    from benchmark.sizing import on, sds

    one = SingleDeviceSharding(device)
    slots = deployment["num_slots"]
    params = on(one, jax.eval_shape(
        weights.init_fn(spec), jax.eval_shape(lambda: jax.random.key(0))))
    cfg, page, prefill, step = _programs(params, spec, deployment,
                                         deployment["kv_pool_tokens"], slots)
    cache = on(one, jax.eval_shape(
        lambda: laguna.init_cache(cfg, page, slots)))
    mbs = page["full"].max_blocks_per_seq
    decode = step.jitted.lower(
        params, cache,
        {k: sds((slots, mbs), jnp.int32, one) for k in laguna.KINDS},
        sds((slots,), jnp.int32, one), sds((slots,), jnp.bool_, one))

    def bucket(pad_len):
        return prefill.jitted.lower(
            params, cache,
            {k: sds((mbs,), jnp.int32, one) for k in laguna.KINDS},
            sds((1, pad_len), jnp.int32, one), sds((), jnp.int32, one),
            sds((), jnp.int32, one), pad_len=pad_len)

    return decode, bucket


def train_setup(spec: dict, job: dict, mesh):
    raise SystemExit(NO_TRAIN)
