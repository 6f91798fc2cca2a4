"""The adapter of the ``axk1`` language model's block
(``"architecture": "axk1"``): latent (multi-head latent) attention in
every layer with YaRN-scaled rotary on a part of each key, a dense
SwiGLU in the first ``first_k_dense_replace`` layers, and in the others
a shared expert beside a routed expert MLP (sigmoid scores, groups of
experts of which ``topk_group`` are kept, no correction bias). The
program's side is ``ray_tpu.models.axk1`` on the paged serving path; the
reference is ``benchmark/reference/axk1.py``.

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
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "rope_theta", "rope_scaling", "first_k_dense_replace",
    "moe_layer_freq", "intermediate_size", "moe_intermediate_size",
    "n_routed_experts", "n_shared_experts", "n_group", "topk_group",
    "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps",
    "max_position_embeddings", "tie_word_embeddings")
NO_TRAIN = ("the axk1 block has no train path: no train cell runs it (at "
            "16 bytes a parameter even the guide's floors need 44.6 GB)")


def check_config(spec: dict) -> None:
    """The keys this block needs, and the program's side of it: a
    checkout whose program lacks the module (a commit from before the
    block was added) exits here, in the driver, before any process is
    started."""
    import os

    program = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "ray_tpu", "models", "axk1.py")
    if not os.path.isfile(program):
        raise SystemExit(f"config {spec.get('name')!r}: this checkout's "
                         "program cannot run the axk1 block: no file "
                         f"{program}")
    missing = sorted(k for k in KEYS if k not in spec)
    if missing:
        raise SystemExit(f"config {spec.get('name')!r}: the axk1 block "
                         f"needs the keys {missing}")
    if (spec["tie_word_embeddings"] or spec["moe_layer_freq"] != 1
            or spec.get("scoring_func") != "sigmoid"
            or spec.get("topk_method") != "none"
            or not spec.get("norm_topk_prob")
            or (spec["rope_scaling"] or {}).get("type") != "yarn"):
        raise SystemExit(
            f"config {spec.get('name')!r}: the axk1 block has an untied "
            "head, a routed MLP in every layer after the dense ones, "
            "sigmoid scores normalised over the chosen with no correction "
            "bias, and YaRN")


# ------------------------------------------------------------------ counts
def _layers(spec, layers):
    return spec["num_hidden_layers"] if layers is None else layers


def _router_width(spec) -> int:
    return spec.get("router_width", spec["n_routed_experts"])


def _routed_layers(spec, layers=None) -> int:
    return max(0, _layers(spec, layers) - spec["first_k_dense_replace"])


def row_width(spec: dict) -> int:
    """What a cached token holds in one layer: (c_kv, k_rope)."""
    return spec["kv_lora_rank"] + spec["qk_rope_head_dim"]


def attention_params(spec: dict) -> int:
    h, H = spec["hidden_size"], spec["num_attention_heads"]
    qr, kr = spec["q_lora_rank"], spec["kv_lora_rank"]
    nope, rope = spec["qk_nope_head_dim"], spec["qk_rope_head_dim"]
    v = spec["v_head_dim"]
    return (h * qr + qr * H * (nope + rope) + h * (kr + rope)
            + kr * H * (nope + v) + H * v * h)


def expert_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["moe_intermediate_size"]


def matrix_params(spec: dict, layers: int | None = None) -> dict:
    """Stored parameters that take part in a matrix multiply, by group
    (of the routed experts: those the configuration holds)."""
    h = spec["hidden_size"]
    n, routed = _layers(spec, layers), _routed_layers(spec, layers)
    return {
        "attention": n * attention_params(spec),
        "dense_mlp": (n - routed) * 3 * h * spec["intermediate_size"],
        "shared_experts": routed * spec["n_shared_experts"]
        * expert_params(spec),
        "experts": routed * spec["n_routed_experts"] * expert_params(spec),
        "router": routed * h * _router_width(spec),
        "head": h * spec["vocab_size"]}


def num_params(spec: dict, layers: int | None = None) -> int:
    """All stored parameters: embedding, head, the matrices above, four
    norms a layer (block input, MLP input, the two latents') and the
    final one."""
    h = spec["hidden_size"]
    n = _layers(spec, layers)
    return (spec["vocab_size"] * h + sum(matrix_params(spec, layers).values())
            + n * (2 * h + spec["q_lora_rank"] + spec["kv_lora_rank"]) + h)


def train_flops_per_token(spec: dict, seq: int) -> float:
    raise SystemExit(NO_TRAIN)


def kv_bytes_per_token(spec: dict) -> int:
    """Bytes one cached token takes over all the layers run: a latent
    row (c_kv, k_rope) a layer, in bf16. (The pool's row is padded to
    whole lanes, 576 -> 640; the padding is no part of this count, so it
    shows as lost roofline.)"""
    return spec["num_hidden_layers"] * row_width(spec) * 2


def paged_mla_decode_counts(spec: dict, live_tokens: float,
                            slots: int) -> dict:
    """What ONE call (one layer) of the absorbed decode kernel has to do:
    read every live token's latent row once, the queries in (as wide as a
    row) and the outputs out (``kv_lora_rank`` wide) for every head; a
    scores product over the row and a values product over its
    ``kv_lora_rank`` columns, for every head and live token."""
    H, W, R = spec["num_attention_heads"], row_width(spec), \
        spec["kv_lora_rank"]
    return {"bytes": live_tokens * W * 2 + slots * H * (W + R) * 2,
            "flops": live_tokens * H * (W + R) * 2}


def grouped_expert_matmul_bytes(spec: dict, experts_hit: float,
                                pairs: float, layer_calls: float) -> float:
    """Bytes ONE grouped product of an expert layer has to move, as the
    mean over the ``layer_calls`` the counters cover: the (hidden x
    expert width) matrix of each expert that has a token, and a row in
    and a row out for each (token, expert) pair, in bf16. (The gate and
    up products write float32, which this leaves out: it errs low.)"""
    h, m = spec["hidden_size"], spec["moe_intermediate_size"]
    calls = max(layer_calls, 1.0)
    return (experts_hit / calls) * h * m * 2 + (pairs / calls) * (h + m) * 2


def kernel_counts(spec: dict, kernel: str, **sizes) -> dict:
    """Bytes (and operations) of ONE call of the kernel whose custom
    call carries this instruction name. ``sizes``: ``live_tokens``,
    ``slots`` for the paged kernel; ``experts_hit``, ``pairs``,
    ``layer_calls`` (the engine's ``model_counters``) for the grouped
    product of a decode step, and the same with ``prefill_`` before them
    (``model_counters_prefill``) for a prefill's."""
    if kernel == "paged_mla_decode":
        return paged_mla_decode_counts(spec, sizes["live_tokens"],
                                       sizes["slots"])
    if kernel == "grouped_expert_matmul":           # a decode step's
        return {"bytes": grouped_expert_matmul_bytes(
            spec, sizes["experts_hit"], sizes["pairs"],
            sizes["layer_calls"])}
    if kernel == "grouped_expert_matmul_prefill":
        return {"bytes": grouped_expert_matmul_bytes(
            spec, sizes["prefill_experts_hit"], sizes["prefill_pairs"],
            sizes["prefill_layer_calls"])}
    raise KeyError(f"axk1 counts no kernel named {kernel!r}")


# ----------------------------------------------------------------- program
def program_kwargs(spec: dict) -> dict:
    rs = spec["rope_scaling"]
    return dict(
        vocab_size=spec["vocab_size"], hidden=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        q_rank=spec["q_lora_rank"], kv_rank=spec["kv_lora_rank"],
        nope_dim=spec["qk_nope_head_dim"], rope_dim=spec["qk_rope_head_dim"],
        v_dim=spec["v_head_dim"], rope_theta=float(spec["rope_theta"]),
        yarn=dict(factor=float(rs["factor"]),
                  original_max_seq=rs["original_max_position_embeddings"],
                  beta_fast=float(rs["beta_fast"]),
                  beta_slow=float(rs["beta_slow"]),
                  mscale=float(rs["mscale"]),
                  mscale_all_dim=float(rs["mscale_all_dim"])),
        dense_layers=spec["first_k_dense_replace"],
        mlp_dim=spec["intermediate_size"],
        expert_dim=spec["moe_intermediate_size"],
        shared_dim=spec["n_shared_experts"] * spec["moe_intermediate_size"],
        n_experts=_router_width(spec), n_group=spec["n_group"],
        topk_group=spec["topk_group"], top_k=spec["num_experts_per_tok"],
        experts_held=(spec.get("experts_first", 0),
                      spec["n_routed_experts"]),
        routed_scale=float(spec["routed_scaling_factor"]),
        norm_eps=spec["rms_norm_eps"],
        max_seq=spec["max_position_embeddings"])


def program_config(spec: dict):
    from ray_tpu.models import axk1
    from ray_tpu.ops.rope import YarnScaling

    kw = program_kwargs(spec)
    return axk1.AxK1Config(**dict(kw, yarn=YarnScaling(**kw["yarn"])))


def engine_kwargs(spec: dict, deployment: dict) -> dict:
    """Keyword arguments of ``LLMEngine`` but the weights."""
    return dict(config=program_config(spec), seed=0,
                num_slots=deployment["num_slots"],
                max_seq=deployment["max_seq"], kv_cache="paged",
                kv_pool_tokens=deployment["kv_pool_tokens"],
                kv_block_size=deployment["kv_block_size"],
                prefix_cache="off")


# ----------------------------------------------------------------- weights
def weight_shapes(spec: dict) -> dict:
    """The tree the program's builders take: ``layers`` a LIST, one dict
    a layer (layer 0 is not like the others); a norm's stored ``w``
    scales by ``1 + w``; ``W_kvb`` in its two halves; a routed layer
    holds its router, the shared expert and the held experts' matrices
    stacked on a leading axis."""
    h, H = spec["hidden_size"], spec["num_attention_heads"]
    qr, kr = spec["q_lora_rank"], spec["kv_lora_rank"]
    nope, rope = spec["qk_nope_head_dim"], spec["qk_rope_head_dim"]
    m, G = spec["moe_intermediate_size"], spec["n_routed_experts"]
    ms = spec["n_shared_experts"] * m
    layers = []
    for l in range(spec["num_hidden_layers"]):
        layer = {"attn_norm": (h,), "w_qa": (h, qr), "q_norm": (qr,),
                 "w_qb": (qr, H, nope + rope), "w_kva": (h, kr + rope),
                 "kv_norm": (kr,), "w_kvb_k": (kr, H, nope),
                 "w_kvb_v": (kr, H, spec["v_head_dim"]),
                 "wo": (H, spec["v_head_dim"], h), "mlp_norm": (h,)}
        if l < spec["first_k_dense_replace"]:
            layer.update(w_gate=(h, spec["intermediate_size"]),
                         w_up=(h, spec["intermediate_size"]),
                         w_down=(spec["intermediate_size"], h))
        else:
            layer.update(router=(h, _router_width(spec)),
                         ws_gate=(h, ms), ws_up=(h, ms), ws_down=(ms, h),
                         we_gate=(G, h, m), we_up=(G, h, m),
                         we_down=(G, m, h))
        layers.append(layer)
    return {"embed": (spec["vocab_size"], h), "layers": layers,
            "final_norm": (h,), "lm_head": (h, spec["vocab_size"])}


def weight_stds(spec: dict) -> tuple:
    """Normal draws at ``hidden ** -0.5``; the second matrix of a
    low-rank pair at its own fan-in (``q_lora_rank``, ``kv_lora_rank``)
    ``** -0.5``, so that queries, keys and values are of order one as
    after a full-rank projection; every projection back into the
    residual stream (attention out, dense down, the shared and the routed
    experts' down) scaled down by ``sqrt(2 L)`` so that activations stay
    of order one through the depth; a routed expert's down projection
    drawn ``routed_scaling_factor`` times smaller still, because the
    routed sum is multiplied by that factor before it joins the stream (a
    trained model's experts have learned their output under the factor;
    drawn without it the routed sum is 2.5 times every other term of the
    stream, and ONE flipped router choice on a held expert moved a row's
    logits by 0.13, above what the int8 control reads: PERF.md, PR 31,
    as PR 27 found for the other routed model); norm weights at 0.1 so
    that a dropped ``1 + w`` shows."""
    std = spec["hidden_size"] ** -0.5
    out_std = std / (2 * spec["num_hidden_layers"]) ** 0.5
    return std, {"attn_norm": 0.1, "mlp_norm": 0.1, "final_norm": 0.1,
                 "q_norm": 0.1, "kv_norm": 0.1,
                 "w_qb": spec["q_lora_rank"] ** -0.5,
                 "w_kvb_k": spec["kv_lora_rank"] ** -0.5,
                 "w_kvb_v": spec["kv_lora_rank"] ** -0.5,
                 "wo": out_std, "w_down": out_std, "ws_down": out_std,
                 "we_down": out_std / float(spec["routed_scaling_factor"])}


# ------------------------------------------------- the check's program side
def _programs(params, spec: dict, deployment: dict, pool_tokens: int):
    from ray_tpu.models import axk1

    cfg = program_config(spec)
    page = axk1.page_of(max_seq=deployment["max_seq"],
                        block_size=deployment["kv_block_size"],
                        pool_tokens=pool_tokens)
    return (cfg, page, axk1.make_prefill(params, cfg, page),
            axk1.make_decode_step(params, cfg, page))


def serve_program_logits(params, spec: dict, tokens, deployment: dict, *,
                         prefill: int):
    """Prefill of the first ``prefill`` tokens (expanded attention,
    latents written to the pool), then one teacher-forced decode step
    (absorbed attention over the pool) for each token after them, with
    the builders the engine uses at the engine's slot count, ``max_seq``
    and block size. The scratch pool is SMALL (the blocks this one
    sequence needs and one more), so the check does not double the
    cache. -> (1 + steps, vocab) float32."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import axk1
    from ray_tpu.models.paged_cache import pad_to_block_bucket

    num_slots, bs = deployment["num_slots"], deployment["kv_block_size"]
    toks = np.asarray(tokens)
    total = len(toks)
    cfg, page, prefill_fn, decode = _programs(
        params, spec, deployment, bs * (1 + -(-(total + 1) // bs)))
    alloc = axk1.make_manager(page, num_slots)
    cache = axk1.init_cache(cfg, page, num_slots)
    slot = num_slots - 1                  # not the first: indexing shows
    if not alloc.ensure(slot, prefill + 1):
        raise RuntimeError("the scratch pool is too small for the check")
    P = pad_to_block_bucket(prefill, bs)
    padded = np.zeros((1, P), np.int32)
    padded[0, :prefill] = toks[:prefill]
    cache, lg = prefill_fn(cache, alloc.table_rows(slot),
                           jnp.asarray(padded), prefill, slot)
    rows = [np.asarray(lg, np.float32).reshape(-1)]
    active = np.zeros(num_slots, bool)
    active[slot] = True
    for i in range(total - prefill):
        if not alloc.ensure(slot, prefill + i + 1):
            raise RuntimeError("the scratch pool is too small")
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
    from ray_tpu.models import axk1

    from benchmark import weights
    from benchmark.sizing import on, sds

    one = SingleDeviceSharding(device)
    slots = deployment["num_slots"]
    params = on(one, jax.eval_shape(
        weights.init_fn(spec), jax.eval_shape(lambda: jax.random.key(0))))
    cfg, page, prefill, step = _programs(params, spec, deployment,
                                         deployment["kv_pool_tokens"])
    cache = on(one, jax.eval_shape(
        lambda: axk1.init_cache(cfg, page, slots)))
    mbs = page.max_blocks_per_seq
    decode = step.jitted.lower(
        params, cache, {axk1.KIND: sds((slots, mbs), jnp.int32, one)},
        sds((slots,), jnp.int32, one), sds((slots,), jnp.bool_, one))

    def bucket(pad_len):
        return prefill.jitted.lower(
            params, cache, {axk1.KIND: sds((mbs,), jnp.int32, one)},
            sds((1, pad_len), jnp.int32, one), sds((), jnp.int32, one),
            sds((), jnp.int32, one), pad_len=pad_len)

    return decode, bucket


def train_setup(spec: dict, job: dict, mesh):
    raise SystemExit(NO_TRAIN)
