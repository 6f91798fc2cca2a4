"""The adapter of the ``nemotron_h`` language model's block
(``"architecture": "nemotron_h"``): layers of ONE mixer each by
``hybrid_override_pattern`` (``M`` a Mamba-2 mixer whose recurrent state
lives per slot, ``E`` 512 latent experts of two matrices and a squared
ReLU beside an ungated shared expert, ``*`` an attention without a
position term). The program's side is ``ray_tpu.models.nemotron_h`` on
the paged serving path; the reference is
``benchmark/reference/nemotron_h.py``.

A configuration may hold a SHARE of the routed experts
(``n_routed_experts`` the experts held from ``experts_first`` on,
``router_width`` the router's) and of the vocabulary; the counts below
are of what a configuration's keys say, so the same functions give the
uncut model from its ``published`` keys. ``num_hidden_layers`` cuts the
pattern to its first characters.

Importing it imports no jax. The contract is the table in
``benchmark/README.md``.
"""

from __future__ import annotations

KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
    "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "n_routed_experts", "num_experts_per_tok", "moe_latent_size",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "n_shared_experts", "routed_scaling_factor", "norm_topk_prob",
    "n_group", "topk_group", "norm_eps", "mlp_hidden_act",
    "mamba_hidden_act", "use_conv_bias", "tie_word_embeddings",
    "max_position_embeddings")
PUBLISHED_PARAMS = 120.67e9         # "120B-A12B"; the MTP layer not counted
NO_TRAIN = ("the nemotron_h block has no train path: no train cell runs it "
            "(at 16 bytes a parameter no cut within the floors fits a chip, "
            "and the chunked scan has no backward pass here)")


def check_config(spec: dict) -> None:
    """The keys this block needs, and the program's side of it: a
    checkout whose program lacks the module (a commit from before the
    block was added) exits here, in the driver, before any process is
    started."""
    import os

    name = spec.get("name")
    program = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "ray_tpu", "models", "nemotron_h.py")
    if not os.path.isfile(program):
        raise SystemExit(f"config {name!r}: this checkout's program cannot "
                         f"run the nemotron_h block: no file {program}")
    missing = sorted(k for k in KEYS if k not in spec)
    if missing:
        raise SystemExit(f"config {name!r}: the nemotron_h block needs the "
                         f"keys {missing}")
    if (spec["tie_word_embeddings"] or spec["mlp_hidden_act"] != "relu2"
            or spec["mamba_hidden_act"] != "silu"
            or not spec["use_conv_bias"] or not spec["norm_topk_prob"]
            or spec["n_group"] != 1 or spec["topk_group"] != 1
            or spec["n_shared_experts"] != 1
            or set(pattern(spec)) - set("ME*")
            or len(spec["hybrid_override_pattern"])
            < spec["num_hidden_layers"]
            or spec["mamba_num_heads"] % spec["n_groups"]):
        raise SystemExit(
            f"config {name!r}: the nemotron_h block has an untied head, "
            "relu2 experts without a gate, one shared expert, a silu "
            "convolution with bias, sigmoid scores renormalised over the "
            "chosen with no group limit, a pattern over M, E and * that "
            "covers every layer, and heads in whole groups")
    if "published" in spec:
        uncut = num_params(spec["published"])
        if abs(uncut / PUBLISHED_PARAMS - 1.0) > 1e-3:
            raise SystemExit(f"config {name!r}: the published keys count "
                             f"{uncut / 1e9:.3f} B parameters, not 120.67 B")


# ------------------------------------------------------------------ counts
def pattern(spec: dict, layers: int | None = None) -> str:
    """The mixers that are run, one character a layer."""
    n = spec["num_hidden_layers"] if layers is None else layers
    return spec["hybrid_override_pattern"][:n]


def router_width(spec: dict) -> int:
    return spec.get("router_width", spec["n_routed_experts"])


def d_inner(spec: dict) -> int:
    return spec["mamba_num_heads"] * spec["mamba_head_dim"]


def conv_dim(spec: dict) -> int:
    return d_inner(spec) + 2 * spec["n_groups"] * spec["ssm_state_size"]


def expert_params(spec: dict) -> int:
    return 2 * spec["moe_latent_size"] * spec["moe_intermediate_size"]


def mixer_params(spec: dict) -> dict:
    """Stored parameters of ONE layer of each kind, its norm included (of
    the routed experts: those the configuration holds)."""
    h, di = spec["hidden_size"], d_inner(spec)
    q = spec["num_attention_heads"] * spec["head_dim"]
    kv = spec["num_key_value_heads"] * spec["head_dim"]
    return {
        "M": (h + h * (2 * di + 2 * spec["n_groups"] * spec["ssm_state_size"]
                       + spec["mamba_num_heads"])
              + (spec["conv_kernel"] + 1) * conv_dim(spec)
              + 3 * spec["mamba_num_heads"] + di + di * h),
        "*": h + 2 * h * q + 2 * h * kv,
        "E": (h + h * router_width(spec) + router_width(spec)
              + 2 * h * spec["moe_latent_size"]
              + 2 * h * spec["moe_shared_expert_intermediate_size"]
              + spec["n_routed_experts"] * expert_params(spec))}


def matrix_params(spec: dict, layers: int | None = None) -> dict:
    """Stored parameters that take part in a matrix multiply, by group."""
    h, di = spec["hidden_size"], d_inner(spec)
    p = pattern(spec, layers)
    q = spec["num_attention_heads"] * spec["head_dim"]
    kv = spec["num_key_value_heads"] * spec["head_dim"]
    nE = p.count("E")
    return {
        "ssm": p.count("M") * (h * (di + conv_dim(spec)
                                    + spec["mamba_num_heads"]) + di * h),
        "attention": p.count("*") * (2 * h * q + 2 * h * kv),
        "latent": nE * 2 * h * spec["moe_latent_size"],
        "shared_experts": nE * 2 * h
        * spec["moe_shared_expert_intermediate_size"],
        "experts": nE * spec["n_routed_experts"] * expert_params(spec),
        "router": nE * h * router_width(spec),
        "head": h * spec["vocab_size"]}


def num_params(spec: dict, layers: int | None = None) -> int:
    """All stored parameters: embedding, head, every layer, final norm."""
    each = mixer_params(spec)
    return (2 * spec["vocab_size"] * spec["hidden_size"]
            + sum(each[k] for k in pattern(spec, layers))
            + spec["hidden_size"])


def active_params(spec: dict, layers: int | None = None) -> int:
    """Parameters one token passes through: all but the experts it is
    not routed to."""
    idle = router_width(spec) - spec["num_experts_per_tok"]
    held_all = dict(spec, n_routed_experts=router_width(spec))
    return (num_params(held_all, layers)
            - pattern(spec, layers).count("E") * idle * expert_params(spec))


def train_flops_per_token(spec: dict, seq: int) -> float:
    raise SystemExit(NO_TRAIN)


def kv_bytes_per_token(spec: dict) -> int:
    """Bytes of keys and values one cached token takes over the
    attention layers that are run (bf16)."""
    return (pattern(spec).count("*") * spec["num_key_value_heads"]
            * 2 * spec["head_dim"] * 2)


def state_bytes_per_slot(spec: dict) -> int:
    """Bytes one sequence's recurrent state (float32) and convolution
    columns (bf16) take over the state-space layers that are run,
    whatever its length."""
    return pattern(spec).count("M") * (
        4 * d_inner(spec) * spec["ssm_state_size"]
        + 2 * (spec["conv_kernel"] - 1) * conv_dim(spec))


def ssm_decode_update_bytes(spec: dict, slots_live: float,
                            layer_calls: float) -> float:
    """Bytes ONE call (one layer) of the state update has to move, as
    the mean over the ``layer_calls`` the counters cover: for each slot
    that runs its (heads x P x N) float32 state read and written, ``dt
    x`` in and ``y`` out at d_in float32 each, a decay a head and the
    group's B and C. (The kernel is handed the decay repeated over P,
    another d_in float32 a slot, which this leaves out: it errs low.)"""
    di, H = d_inner(spec), spec["mamba_num_heads"]
    slot = (2 * 4 * di * spec["ssm_state_size"] + 2 * 4 * di + 4 * H
            + 2 * 4 * spec["n_groups"] * spec["ssm_state_size"])
    return slots_live / max(layer_calls, 1.0) * slot


def grouped_expert_matmul_bytes(spec: dict, experts_hit: float,
                                pairs: float, layer_calls: float) -> float:
    """Bytes ONE grouped product of an expert layer has to move, as the
    mean over the ``layer_calls`` the counters cover: the (latent x
    expert width) matrix of each expert HIT, and a row in and a row out
    for each (token, expert) pair, in bf16 (a layer runs two products,
    up and down, of the same count; the up product writes float32,
    which this leaves out: it errs low)."""
    l, m = spec["moe_latent_size"], spec["moe_intermediate_size"]
    calls = max(layer_calls, 1.0)
    return (experts_hit / calls) * l * m * 2 + (pairs / calls) * (l + m) * 2


def paged_decode_bytes(spec: dict, live_tokens: float, slots: int) -> float:
    """Bytes ONE call (one attention layer) of the paged decode kernel
    has to move: the live keys and values once, queries in, outputs out."""
    q = spec["num_attention_heads"] * spec["head_dim"]
    row = spec["num_key_value_heads"] * 2 * spec["head_dim"] * 2
    return live_tokens * row + 2 * slots * q * 2


def kernel_counts(spec: dict, kernel: str, **sizes) -> dict:
    """Bytes of ONE call of the kernel whose custom call carries this
    instruction name. ``sizes``: ``slots_live``, ``layer_calls`` (the
    engine's ``model_counters`` ``ssm_slots_live``, ``ssm_layer_calls``)
    for the state update; ``experts_hit``, ``pairs``, ``layer_calls`` for
    the grouped product of a decode step, the same with ``prefill_``
    before them for a prefill's; ``live_tokens``, ``slots`` for the paged
    kernel."""
    if kernel == "ssm_decode_update":
        return {"bytes": ssm_decode_update_bytes(
            spec, sizes["slots_live"], sizes["layer_calls"])}
    if kernel == "grouped_expert_matmul":
        return {"bytes": grouped_expert_matmul_bytes(
            spec, sizes["experts_hit"], sizes["pairs"],
            sizes["layer_calls"])}
    if kernel == "grouped_expert_matmul_prefill":
        return {"bytes": grouped_expert_matmul_bytes(
            spec, sizes["prefill_experts_hit"], sizes["prefill_pairs"],
            sizes["prefill_layer_calls"])}
    if kernel == "paged_decode_attention":
        return {"bytes": paged_decode_bytes(spec, sizes["live_tokens"],
                                            sizes["slots"])}
    raise KeyError(f"nemotron_h counts no kernel named {kernel!r}")


# ----------------------------------------------------------------- program
def program_kwargs(spec: dict) -> dict:
    return dict(
        vocab_size=spec["vocab_size"], hidden=spec["hidden_size"],
        pattern=pattern(spec), ssm_heads=spec["mamba_num_heads"],
        ssm_head_dim=spec["mamba_head_dim"], ssm_groups=spec["n_groups"],
        ssm_state=spec["ssm_state_size"], conv_kernel=spec["conv_kernel"],
        chunk=spec["chunk_size"], n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"], head_dim=spec["head_dim"],
        n_experts=router_width(spec), top_k=spec["num_experts_per_tok"],
        experts_held=(spec.get("experts_first", 0),
                      spec["n_routed_experts"]),
        latent=spec["moe_latent_size"],
        expert_dim=spec["moe_intermediate_size"],
        shared_dim=spec["moe_shared_expert_intermediate_size"],
        routed_scale=float(spec["routed_scaling_factor"]),
        norm_eps=spec["norm_eps"], max_seq=spec["max_position_embeddings"])


def program_config(spec: dict):
    from ray_tpu.models import nemotron_h

    return nemotron_h.NemotronHConfig(**program_kwargs(spec))


def engine_kwargs(spec: dict, deployment: dict) -> dict:
    """Keyword arguments of ``LLMEngine`` but the weights. The recurrent
    state is no figure of the cell file: the model sizes it from the
    slots."""
    return dict(config=program_config(spec), seed=0,
                num_slots=deployment["num_slots"],
                max_seq=deployment["max_seq"], kv_cache="paged",
                kv_pool_tokens=deployment["kv_pool_tokens"],
                kv_block_size=deployment["kv_block_size"],
                prefix_cache="off")


# ----------------------------------------------------------------- weights
def weight_shapes(spec: dict) -> dict:
    """The tree the program's builders take: ``layers`` a LIST, one dict
    a layer by its character of the pattern; a norm's stored ``w`` scales
    by ``1 + w``; ``conv_w`` is (taps, channels); an expert layer holds
    its router and correction bias, the latent projections, the shared
    expert and the HELD experts' two matrices stacked on a leading axis."""
    h, H = spec["hidden_size"], spec["mamba_num_heads"]
    G, N, di = spec["n_groups"], spec["ssm_state_size"], d_inner(spec)
    D, dc = spec["head_dim"], conv_dim(spec)
    l, m = spec["moe_latent_size"], spec["moe_intermediate_size"]
    ms, held = spec["moe_shared_expert_intermediate_size"], \
        spec["n_routed_experts"]
    kinds = {
        "M": {"norm": (h,), "w_in": (h, 2 * di + 2 * G * N + H),
              "conv_w": (spec["conv_kernel"], dc), "conv_b": (dc,),
              "dt_bias": (H,), "A_log": (H,), "D": (H,),
              "gate_norm": (di,), "w_out": (di, h)},
        "*": {"norm": (h,), "wq": (h, spec["num_attention_heads"], D),
              "wk": (h, spec["num_key_value_heads"], D),
              "wv": (h, spec["num_key_value_heads"], D),
              "wo": (spec["num_attention_heads"], D, h)},
        "E": {"norm": (h,), "router": (h, router_width(spec)),
              "router_bias": (router_width(spec),),
              "w_fc1": (h, l), "w_fc2": (l, h),
              "ws_up": (h, ms), "ws_down": (ms, h),
              "we_up": (held, l, m), "we_down": (held, m, l)}}
    return {"embed": (spec["vocab_size"], h),
            "layers": [dict(kinds[k]) for k in pattern(spec)],
            "final_norm": (h,), "lm_head": (h, spec["vocab_size"])}


def weight_stds(spec: dict) -> tuple:
    """Normal draws, a matrix at its fan-in ``** -0.5``; the embedding at
    1.0, so that a token's own row is of the stream's order, as a token's
    identity is in a trained model's stream (at the default ``hidden **
    -0.5`` the row is a hundredth of the first mixer's output, and the
    stream is what the positive means of relu² and silu make of it: over
    192 sequences under greedy decoding the held experts' largest load
    was 10-11 times the mean and 60% of them were hit, by another set
    for each seed; at 1.0 it is 2.4-4.1 times and 127-128 of 128, the
    deployment's bytes: PERF.md, PR 39); every projection
    back into the residual stream (``w_out``, ``wo``, ``w_fc2``,
    ``ws_down``) scaled down by ``sqrt(2 L)``; an expert's down
    projection drawn ``routed_scaling_factor`` times smaller, because the
    routed sum is multiplied by that factor (PERF.md, PR 27 and PR 31);
    norm weights at 0.1 so that a dropped ``1 + w`` shows; the
    convolution's taps at 0.5 (four of them: a sum of order one) and its
    bias at 0.1. ``dt_bias`` and ``D`` at 1.0, ``A_log`` at 2.0:
    ``dt_raw`` is of order one, so ``dt = softplus(dt_raw + dt_bias)``
    spans 0.1-2.4 and ``-A = exp(A_log)`` 0.04-27 (5% to 95%), and
    ``exp(dt A)`` over tokens and heads lies between nothing and 0.99
    (quartiles 0.09, 0.57, 0.90): three heads in ten remember 4 to 256
    tokens, four in ten less than one, one in a hundred more than 256. A
    state that died in a step everywhere, or never decayed, would leave
    the comparison blind to the recurrence. (Every draw has mean zero, so
    the published initialisation, ``dt`` in 0.001-0.1 through a negative
    bias, cannot be drawn; a wide ``A_log`` gives its range of memory.)"""
    back = (2 * spec["num_hidden_layers"]) ** -0.5
    q = spec["num_attention_heads"] * spec["head_dim"]
    return spec["hidden_size"] ** -0.5, {
        "embed": 1.0,
        "norm": 0.1, "gate_norm": 0.1, "final_norm": 0.1, "conv_w": 0.5,
        "conv_b": 0.1, "dt_bias": 1.0, "A_log": 2.0, "D": 1.0,
        "router_bias": 0.01,
        "w_out": d_inner(spec) ** -0.5 * back, "wo": q ** -0.5 * back,
        "w_fc2": spec["moe_latent_size"] ** -0.5 * back,
        "ws_down": spec["moe_shared_expert_intermediate_size"] ** -0.5 * back,
        "we_up": spec["moe_latent_size"] ** -0.5,
        "we_down": spec["moe_intermediate_size"] ** -0.5
        / float(spec["routed_scaling_factor"])}


# ------------------------------------------------- the check's program side
def _programs(params, spec: dict, deployment: dict):
    from ray_tpu.models import nemotron_h
    from ray_tpu.models.paged_cache import PagedConfig

    cfg = program_config(spec)
    bs = deployment["kv_block_size"]
    page = PagedConfig(
        num_blocks=1 + -(-deployment["kv_pool_tokens"] // bs), block_size=bs,
        max_seq=deployment["max_seq"])
    return (cfg, page, nemotron_h.make_prefill(params, cfg, page),
            nemotron_h.make_decode_step(params, cfg, page))


def _engine_serving(params):
    """The engine of this process that serves ``params``, if one does,
    for a caller that hands ``serve_program_logits`` no engine. No run
    of the benchmark comes here since PR 41 (``worker_serve.check``
    hands the replica's engine over); it stays for
    ``tests/test_nemotron_h.py``, a tier-1 file that calls it by name
    and that a ``benchmark`` PR may not edit (PERF.md section 7)."""
    import gc

    from ray_tpu.serve.llm import LLMEngine

    for obj in gc.get_objects():
        if isinstance(obj, LLMEngine) and obj.params is params:
            return obj
    return None


def _neighbours(slots: int) -> list:
    """Slots that run beside the compared one (the last): the first, the
    middle and the one before it, so that the state update compacts
    running slots from both ends of the state over a gap of idle ones."""
    return sorted({0, slots // 2, slots - 2} & set(range(slots - 1)))


def serve_program_logits(params, spec: dict, tokens, deployment: dict, *,
                         prefill: int, engine=None):
    """Prefill of the first ``prefill`` tokens, then one teacher-forced
    decode step for each token after them, through the KV pool AND the
    recurrent state AT THE CELL'S SLOTS: the sequence sits in the LAST
    slot, and up to three other slots (``_neighbours``) hold shorter
    prompts of other tokens and run in every step beside it. ->
    (1 + steps, vocab) float32.

    Where the caller hands over the ``engine`` that serves these weights
    (a run of the cell: ``worker_serve.check``), the programs, the
    manager and the cache are THE ENGINE'S OWN, borrowed while it is
    idle: the compiled decode step and prefills the window times, on the
    buffers it times them on. The engine's
    recurrent state (21 MB a slot, 4.1 GB) is most of what the chip has
    left beside the weights and a second one of its size does not fit,
    so a scratch copy at these slots cannot stand beside it. The slots
    are released afterwards; what the state's rows then hold does not
    matter, because a prefill writes a slot whole. Where no engine does
    (``control.py``, the tests), the same builders at the deployment's
    sizes over scratch caches."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import nemotron_h
    from ray_tpu.models.paged_cache import pad_to_block_bucket

    bs, slots = deployment["kv_block_size"], deployment["num_slots"]
    if engine is None:
        engine = _engine_serving(params)
    elif engine.params is not params:
        raise RuntimeError("the engine handed over serves other weights")
    if engine is None:
        cfg, page, prefill_fn, decode = _programs(params, spec, deployment)
        alloc = nemotron_h.make_manager(cfg, page, slots)
        cache = nemotron_h.init_cache(cfg, page, slots)
    else:
        if (engine.num_slots != slots or engine._flight is not None
                or any(r is not None for r in engine._slots)
                or engine.stats()["queued"]):
            raise RuntimeError("the check borrows an idle engine of the "
                               "deployment's slots; this one is neither")
        prefill_fn, decode = engine._prefill, engine._decode
        alloc, cache = engine._alloc, engine._cache
    # a program's counters are its output alone (the engine's cache
    # holds None there, so this is the signature the engine compiles)
    cache = dict(cache, counters=None)

    toks = np.asarray(tokens)
    last = slots - 1
    # slot -> (its tokens, how many of them its prefill takes); the
    # compared slot is prefilled last, so ``lg`` below is its
    seqs = {slot: (np.roll(toks, 17 * (j + 1)), max(1, prefill // (j + 2)))
            for j, slot in enumerate(_neighbours(slots))}
    seqs[last] = (toks, prefill)
    try:
        for slot, (seq, n) in seqs.items():
            if not alloc.ensure(slot, n + 1):
                raise RuntimeError("the pool is too small for the check")
            padded = np.zeros((1, pad_to_block_bucket(n, bs)), np.int32)
            padded[0, :n] = seq[:n]
            cache, lg = prefill_fn(cache, alloc.table_rows(slot),
                                   jnp.asarray(padded), n, slot)
            cache = dict(cache, counters=None)
        rows = [np.asarray(lg, np.float32).reshape(-1)]
        active = np.zeros(slots, bool)
        active[list(seqs)] = True
        for i in range(len(toks) - prefill):
            fed = np.zeros(slots, np.int32)
            for slot, (seq, n) in seqs.items():
                if not alloc.ensure(slot, n + i + 1):
                    raise RuntimeError("the pool is too small for the check")
                fed[slot] = seq[n + i]
            cache, lg = decode(cache, alloc.device_tables(),
                               jnp.asarray(fed), jnp.asarray(active))
            cache = dict(cache, counters=None)
            rows.append(np.asarray(lg, np.float32)[last])
    finally:
        for slot in seqs:
            alloc.release(slot)
        if engine is not None:
            engine._cache = cache
    return np.stack(rows)


def train_program_loss_and_grads(params, spec: dict, tokens, rules=None):
    raise SystemExit(NO_TRAIN)


# ------------------------------------------------- programs from shapes alone
def lower_serve_programs(spec: dict, deployment: dict, device):
    """(decode step, bucket -> prefill) lowered for one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.models import nemotron_h

    from benchmark import weights
    from benchmark.sizing import on, sds

    one = SingleDeviceSharding(device)
    slots = deployment["num_slots"]
    params = on(one, jax.eval_shape(
        weights.init_fn(spec), jax.eval_shape(lambda: jax.random.key(0))))
    cfg, page, prefill, step = _programs(params, spec, deployment)
    cache = on(one, jax.eval_shape(     # counters None: as the engine calls
        lambda: dict(nemotron_h.init_cache(cfg, page, slots), counters=None)))
    mbs = page.max_blocks_per_seq
    decode = step.jitted.lower(
        params, cache, {"full": sds((slots, mbs), jnp.int32, one)},
        sds((slots,), jnp.int32, one), sds((slots,), jnp.bool_, one))

    def bucket(pad_len):
        return prefill.jitted.lower(
            params, cache, {"full": sds((mbs,), jnp.int32, one)},
            sds((1, pad_len), jnp.int32, one), sds((), jnp.int32, one),
            sds((), jnp.int32, one), pad_len=pad_len)

    return decode, bucket


def train_setup(spec: dict, job: dict, mesh):
    raise SystemExit(NO_TRAIN)
