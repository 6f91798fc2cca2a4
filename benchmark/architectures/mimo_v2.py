"""The adapter of the ``mimo_v2`` language model's block
(``"architecture": "mimo_v2"``): full and sliding-window attention
layers mixed by ``hybrid_layer_pattern`` (keys 192 wide, values 128,
rotary on a third of a key, a sink logit in the window layers), a dense
SwiGLU in the layers ``moe_layer_freq`` marks 0 and a routed expert MLP
(sigmoid scores, ``noaux_tc`` choice, no shared expert) in the others.
The program's side is ``ray_tpu.models.mimo_v2`` on the paged serving
path; the reference is ``benchmark/reference/mimo_v2.py``.

A configuration of this block may be ONE CHIP'S SHARE of an
expert-parallel deployment: ``n_routed_experts`` then counts the experts
held here (``experts_first`` on), ``router_width`` the experts the
router scores (the published count), ``vocab_size`` the rows of the
vocabulary held. Every count below is of what the configuration's keys
say, so the same functions give the uncut model from its ``published``
keys (``router_width`` left out: the router is as wide as the experts).

Importing it imports no jax. The contract is the table in
``benchmark/README.md``.
"""

from __future__ import annotations

KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "swa_num_key_value_heads", "head_dim",
    "v_head_dim", "partial_rotary_factor", "rope_theta", "swa_rope_theta",
    "sliding_window", "attention_value_scale", "hybrid_layer_pattern",
    "moe_layer_freq", "intermediate_size", "moe_intermediate_size",
    "n_routed_experts", "num_experts_per_tok", "layernorm_epsilon",
    "max_position_embeddings", "tie_word_embeddings")


def check_config(spec: dict) -> None:
    """The keys this block needs, and the program's side of it: a
    checkout whose program lacks the module (a commit from before the
    block was added) exits here, in the driver, before any process is
    started."""
    import os

    program = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "ray_tpu", "models", "mimo_v2.py")
    if not os.path.isfile(program):
        raise SystemExit(f"config {spec.get('name')!r}: this checkout's "
                         "program cannot run the mimo_v2 block: no file "
                         f"{program}")
    missing = sorted(k for k in KEYS if k not in spec)
    if missing:
        raise SystemExit(f"config {spec.get('name')!r}: the mimo_v2 block "
                         f"needs the keys {missing}")
    if spec["tie_word_embeddings"] or spec.get("n_shared_experts"):
        raise SystemExit(f"config {spec.get('name')!r}: the mimo_v2 block "
                         "has an untied head and no shared expert")


# ------------------------------------------------------------------ counts
def _layers(spec, layers):
    return spec["num_hidden_layers"] if layers is None else layers


def _router_width(spec) -> int:
    return spec.get("router_width", spec["n_routed_experts"])


def _kv_heads(spec, kind: str) -> int:
    return spec["num_key_value_heads" if kind == "full"
                else "swa_num_key_value_heads"]


def layer_kinds(spec: dict, layers: int | None = None) -> list:
    """"full" or "window" for each layer that is run."""
    return ["window" if k else "full"
            for k in spec["hybrid_layer_pattern"][:_layers(spec, layers)]]


def attention_params(spec: dict, kind: str) -> int:
    h, H = spec["hidden_size"], spec["num_attention_heads"]
    D, Dv, KV = spec["head_dim"], spec["v_head_dim"], _kv_heads(spec, kind)
    return h * H * D + h * KV * D + h * KV * Dv + H * Dv * h


def expert_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["moe_intermediate_size"]


def matrix_params(spec: dict, layers: int | None = None) -> dict:
    """Stored parameters that take part in a matrix multiply, by group
    (of the experts: those the configuration holds)."""
    h = spec["hidden_size"]
    n = _layers(spec, layers)
    kinds = layer_kinds(spec, layers)
    routed = sum(spec["moe_layer_freq"][:n])
    return {
        "attention": sum(attention_params(spec, k) for k in kinds),
        "dense_mlp": (n - routed) * 3 * h * spec["intermediate_size"],
        "experts": routed * spec["n_routed_experts"] * expert_params(spec),
        "router": routed * h * _router_width(spec),
        "head": h * spec["vocab_size"]}


def num_params(spec: dict, layers: int | None = None) -> int:
    """All stored parameters: embedding, head, the matrices above, two
    norms a layer and the final one, a sink logit for each head of a
    window layer, the router's correction bias."""
    h = spec["hidden_size"]
    n = _layers(spec, layers)
    mp = matrix_params(spec, layers)
    routed = sum(spec["moe_layer_freq"][:n])
    windows = layer_kinds(spec, layers).count("window")
    return (spec["vocab_size"] * h + sum(mp.values()) + n * 2 * h + h
            + windows * spec["num_attention_heads"]
            + routed * _router_width(spec))


def train_flops_per_token(spec: dict, seq: int) -> float:
    raise SystemExit("the mimo_v2 block has no train path: no train cell "
                     "runs it (16 bytes a parameter fit no chip)")


def kv_bytes_per_token(spec: dict, kind: str = "full") -> int:
    """Bytes of keys and values one cached token takes in ONE layer of
    that kind (bf16; keys ``head_dim`` wide, values ``v_head_dim``)."""
    return _kv_heads(spec, kind) * (spec["head_dim"]
                                    + spec["v_head_dim"]) * 2


def paged_hybrid_decode_bytes(spec: dict, kind: str, live_tokens: int,
                              slots: int) -> int:
    """Bytes ONE call of the paged decode kernel of that kind has to
    move: the live keys and values once (for a window layer: what the
    window leaves live, at most ``sliding_window`` tokens a slot), the
    queries in and the outputs out."""
    H = spec["num_attention_heads"]
    return (live_tokens * kv_bytes_per_token(spec, kind)
            + slots * H * (spec["head_dim"] + spec["v_head_dim"]) * 2)


def grouped_expert_matmul_bytes(spec: dict, experts_hit: float,
                                pairs: float, layer_calls: float) -> float:
    """Bytes ONE grouped product of an expert layer has to move, as the
    mean over the ``layer_calls`` the counters cover: the (hidden x
    expert width) matrix of each expert that has a token, and a row in
    and a row out for each (token, expert) pair, in bf16. An expert with
    no token is not read, by the kernel or by this count. (The three
    products of a layer move the same matrix size; the gate and up
    products write float32, which this leaves out: it errs low.)"""
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
    raise KeyError(f"mimo_v2 counts no kernel named {kernel!r}")


# ----------------------------------------------------------------- program
def program_kwargs(spec: dict) -> dict:
    n = spec["num_hidden_layers"]
    return dict(
        vocab_size=spec["vocab_size"], hidden=spec["hidden_size"],
        n_layers=n, n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        swa_n_kv_heads=spec["swa_num_key_value_heads"],
        head_dim=spec["head_dim"], v_head_dim=spec["v_head_dim"],
        rotary_dim=int(spec["partial_rotary_factor"] * spec["head_dim"]),
        rope_theta=float(spec["rope_theta"]),
        swa_rope_theta=float(spec["swa_rope_theta"]),
        window=spec["sliding_window"],
        value_scale=spec["attention_value_scale"],
        layer_kinds=tuple(spec["hybrid_layer_pattern"][:n]),
        moe_layers=tuple(spec["moe_layer_freq"][:n]),
        mlp_dim=spec["intermediate_size"],
        expert_dim=spec["moe_intermediate_size"],
        n_experts=_router_width(spec), top_k=spec["num_experts_per_tok"],
        experts_held=(spec.get("experts_first", 0),
                      spec["n_routed_experts"]),
        routed_scale=spec.get("routed_scaling_factor") or 1.0,
        norm_eps=spec["layernorm_epsilon"],
        max_seq=spec["max_position_embeddings"])


def program_config(spec: dict):
    from ray_tpu.models import mimo_v2

    return mimo_v2.MimoV2Config(**program_kwargs(spec))


def engine_kwargs(spec: dict, deployment: dict) -> dict:
    """Keyword arguments of ``LLMEngine`` but the weights.
    ``kv_pool_tokens`` sizes the full layers' pool; the window layers'
    pool follows from the slots and the window (three blocks a slot)."""
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
    ``1 + w``; a routed layer holds its router, the correction bias and
    the held experts' matrices stacked on a leading axis."""
    h, H = spec["hidden_size"], spec["num_attention_heads"]
    D, Dv = spec["head_dim"], spec["v_head_dim"]
    m, G = spec["moe_intermediate_size"], spec["n_routed_experts"]
    layers = []
    for l, kind in enumerate(layer_kinds(spec)):
        KV = _kv_heads(spec, kind)
        layer = {"attn_norm": (h,), "wq": (h, H, D), "wk": (h, KV, D),
                 "wv": (h, KV, Dv), "wo": (H, Dv, h), "mlp_norm": (h,)}
        if kind == "window":
            layer["sink"] = (H,)
        if spec["moe_layer_freq"][l]:
            layer.update(router=(h, _router_width(spec)),
                         router_bias=(_router_width(spec),),
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
    """Normal draws at ``hidden ** -0.5``; every projection back into
    the residual stream (attention out, dense down, an expert's down)
    scaled down by ``sqrt(2 L)`` so that activations stay of order one
    through the depth; norm weights at 0.1 so that a dropped ``1 + w``
    shows; the router's correction bias at 0.01: the published bias is
    what an auxiliary-loss-free balancer leaves, small shifts that even
    the experts' loads out, and a draw at 0.1 instead made one expert's
    load 6.4 times the mean and the pairs computed here swing with the
    seed (PERF.md, PR 27); sink logits at 1, the size of a score. (An expert's down projection drawn without the
    depth's factor made ONE flipped router choice on a held expert move
    a row's logits by 0.13, above what the int8 control reads: PERF.md,
    PR 27.)"""
    std = spec["hidden_size"] ** -0.5
    out_std = std / (2 * spec["num_hidden_layers"]) ** 0.5
    return std, {"attn_norm": 0.1, "mlp_norm": 0.1, "final_norm": 0.1,
                 "wo": out_std, "w_down": out_std, "we_down": out_std,
                 "sink": 1.0, "router_bias": 0.01}


# ------------------------------------------------- the check's program side
def _programs(params, spec: dict, deployment: dict, pool_tokens: int):
    from ray_tpu.models import mimo_v2

    cfg = program_config(spec)
    slots = deployment["num_slots"]
    page = mimo_v2.pages(cfg, num_slots=slots,
                         max_seq=deployment["max_seq"],
                         block_size=deployment["kv_block_size"],
                         pool_tokens=pool_tokens)
    return (cfg, page, mimo_v2.make_prefill(params, cfg, page),
            mimo_v2.make_decode_step(params, cfg, page))


def serve_program_logits(params, spec: dict, tokens, deployment: dict, *,
                         prefill: int):
    """Prefill of the first ``prefill`` tokens, then one teacher-forced
    decode step for each token after them through scratch pools of both
    kinds, with the builders the engine uses at the engine's slot count,
    ``max_seq`` and block size, and the engine's order of work on the
    tables (trim what the window has passed, then grow). -> (1 + steps,
    vocab) float32."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import mimo_v2
    from ray_tpu.models.paged_cache import pad_to_block_bucket

    num_slots, bs = deployment["num_slots"], deployment["kv_block_size"]
    toks = np.asarray(tokens)
    total = len(toks)
    cfg, page, prefill_fn, decode = _programs(
        params, spec, deployment, bs * (1 + -(-(total + 1) // bs)))
    alloc = mimo_v2.make_manager(cfg, page, num_slots)
    cache = mimo_v2.init_cache(cfg, page, num_slots)
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
    raise SystemExit("the mimo_v2 block has no train path: no train cell "
                     "runs it (16 bytes a parameter fit no chip)")


# ------------------------------------------------- programs from shapes alone
def lower_serve_programs(spec: dict, deployment: dict, device):
    """(decode step, bucket -> prefill) lowered for one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.models import mimo_v2

    from benchmark import weights
    from benchmark.sizing import on, sds

    one = SingleDeviceSharding(device)
    slots = deployment["num_slots"]
    params = on(one, jax.eval_shape(
        weights.init_fn(spec), jax.eval_shape(lambda: jax.random.key(0))))
    cfg, page, prefill, step = _programs(params, spec, deployment,
                                         deployment["kv_pool_tokens"])
    cache = on(one, jax.eval_shape(
        lambda: mimo_v2.init_cache(cfg, page, slots)))
    mbs = page["full"].max_blocks_per_seq
    decode = step.jitted.lower(
        params, cache,
        {k: sds((slots, mbs), jnp.int32, one) for k in mimo_v2.KINDS},
        sds((slots,), jnp.int32, one), sds((slots,), jnp.bool_, one))

    def bucket(pad_len):
        return prefill.jitted.lower(
            params, cache,
            {k: sds((mbs,), jnp.int32, one) for k in mimo_v2.KINDS},
            sds((1, pad_len), jnp.int32, one), sds((), jnp.int32, one),
            sds((), jnp.int32, one), pad_len=pad_len)

    return decode, bucket


def train_setup(spec: dict, job: dict, mesh):
    raise SystemExit("the mimo_v2 block has no train path: no train cell "
                     "runs it (16 bytes a parameter fit no chip)")
