"""The adapter of the ``sdar_moe`` language model (``"architecture":
"sdar"``): the Qwen3-MoE layer (grouped-query attention with an RMSNorm
over the head's width on every query and key head before rotary, a
softmax-routed SwiGLU expert MLP in every layer, no shared expert, an
untied head) under a block-causal mask, generating by diffusion over
blocks of ``block_length`` positions. The program's side is
``ray_tpu.models.sdar`` on the paged serving path, where a slot's block
is denoised in place against the cache and then committed; the reference
is ``benchmark/reference/sdar.py``.

The configuration the benchmark runs holds EVERY expert (``num_experts``
= the router's width). The counts below are of what a configuration's
keys say, so the same functions give the uncut model from its
``published`` keys.

Importing it imports no jax. The contract is the table in
``benchmark/README.md``.
"""

from __future__ import annotations

KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "max_position_embeddings",
    "rms_norm_eps", "rope_theta", "rope_scaling", "num_experts",
    "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
    "decoder_sparse_step", "mlp_only_layers", "tie_word_embeddings",
    "attention_bias", "use_sliding_window",
    # generation, under ``assumed`` in the configuration's file
    "block_length", "mask_token_id", "denoising_steps", "remasking")
PUBLISHED_PARAMS = 30.53e9          # the family states 30B-A3B
NO_TRAIN = ("the sdar block has no train path: no train cell runs it (the "
            "family trains under a diffusion loss over noised blocks and a "
            "block-causal mask, which the train path has not, beside "
            "dropped tokens in its expert layer; at 16 bytes a parameter "
            "it fits only as one of 8 chips that share each layer)")


def check_config(spec: dict) -> None:
    """The keys this block needs, and the program's side of it: a
    checkout whose program lacks the module (a commit from before the
    block was added) exits here, in the driver, before any process is
    started."""
    import os

    name = spec.get("name")
    program = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "ray_tpu", "models", "sdar.py")
    if not os.path.isfile(program):
        raise SystemExit(f"config {name!r}: this checkout's program cannot "
                         f"run the sdar block: no file {program}")
    missing = sorted(k for k in KEYS if k not in spec)
    if missing:
        raise SystemExit(f"config {name!r}: the sdar block needs the keys "
                         f"{missing}")
    if (spec["tie_word_embeddings"] or spec["attention_bias"]
            or spec["use_sliding_window"] or spec["rope_scaling"]
            or spec["decoder_sparse_step"] != 1 or spec["mlp_only_layers"]
            or not spec["norm_topk_prob"]
            or spec["remasking"] != "low_confidence_static"):
        raise SystemExit(
            f"config {name!r}: the sdar block has an untied head, no bias, "
            "no window and plain rotary, a routed MLP in every layer whose "
            "chosen probabilities are renormalised, and decides positions "
            "by low_confidence_static")
    if "published" in spec:
        uncut = num_params(spec["published"])
        if abs(uncut / PUBLISHED_PARAMS - 1.0) > 1e-2:
            raise SystemExit(f"config {name!r}: the published keys count "
                             f"{uncut / 1e9:.3f} B parameters, not 30.5 B")


# ------------------------------------------------------------------ counts
def _layers(spec, layers):
    return spec["num_hidden_layers"] if layers is None else layers


def router_width(spec: dict) -> int:
    return spec.get("router_width", spec["num_experts"])


def attention_params(spec: dict) -> int:
    """Of one layer: q, k, v and the output projection."""
    h, D = spec["hidden_size"], spec["head_dim"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    return h * H * D + 2 * h * KV * D + H * D * h


def expert_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["moe_intermediate_size"]


def matrix_params(spec: dict, layers: int | None = None) -> dict:
    """Stored parameters that take part in a matrix multiply, by group
    (of the routed experts: those the configuration holds)."""
    n, h = _layers(spec, layers), spec["hidden_size"]
    return {"attention": n * attention_params(spec),
            "experts": n * spec["num_experts"] * expert_params(spec),
            "router": n * h * router_width(spec),
            "head": h * spec["vocab_size"]}


def num_params(spec: dict, layers: int | None = None) -> int:
    """All stored parameters: embedding, head, the matrices above, two
    norms and the two head norms a layer, and the final norm."""
    h = spec["hidden_size"]
    return (spec["vocab_size"] * h + sum(matrix_params(spec, layers).values())
            + _layers(spec, layers) * 2 * (h + spec["head_dim"]) + h)


def active_params(spec: dict, layers: int | None = None) -> int:
    """Parameters one token passes through: all but the experts it is
    not routed to."""
    idle = spec["num_experts"] - spec["num_experts_per_tok"]
    return (num_params(spec, layers)
            - _layers(spec, layers) * idle * expert_params(spec))


def train_flops_per_token(spec: dict, seq: int) -> float:
    raise SystemExit(NO_TRAIN)


def kv_bytes_per_token(spec: dict) -> int:
    """Bytes of keys and values ONE layer keeps for one cached token
    (bf16), as the dense adapter counts it."""
    return spec["num_key_value_heads"] * 2 * spec["head_dim"] * 2


def block_step_attention_bytes(spec: dict, live_tokens: float,
                               slots: float, block_length: int) -> float:
    """Bytes ONE call (one layer) of the paged decode kernel has to move
    in a block step: every live token's keys and values once (the rows
    of the blocks being denoised are among the allocator's live tokens:
    their block of the pool is taken before the step), and
    ``block_length`` rows of every query head a slot in and out."""
    queries = (block_length * spec["num_attention_heads"]
               * spec["head_dim"] * 2)
    return live_tokens * kv_bytes_per_token(spec) + slots * 2 * queries


def grouped_expert_matmul_bytes(spec: dict, experts_hit: float,
                                pairs: float, layer_calls: float) -> float:
    """Bytes ONE grouped product of an expert layer has to move, as the
    mean over the ``layer_calls`` the counters cover: the (hidden x
    expert width) matrix of each expert HIT, and a row in and a row out
    for each (token, expert) pair, in bf16. (The gate and up products
    write float32, which this leaves out: it errs low.)"""
    h, m = spec["hidden_size"], spec["moe_intermediate_size"]
    calls = max(layer_calls, 1.0)
    return (experts_hit / calls) * h * m * 2 + (pairs / calls) * (h + m) * 2


def kernel_counts(spec: dict, kernel: str, **sizes) -> dict:
    """Bytes of ONE call of the kernel whose custom call carries this
    instruction name. ``sizes``: ``live_tokens`` (the allocator's),
    ``slots`` (the cell's) and ``block_length`` (the configuration's)
    for the paged kernel, which here runs in the block step;
    ``experts_hit``, ``pairs``, ``layer_calls`` (the engine's
    ``model_counters``) for the grouped product of a block step, and the
    same with ``prefill_`` before them for a prefill's."""
    if kernel == "paged_decode_attention":
        return {"bytes": block_step_attention_bytes(
            spec, sizes["live_tokens"], sizes["slots"],
            sizes["block_length"])}
    if kernel == "grouped_expert_matmul":           # a block step's
        return {"bytes": grouped_expert_matmul_bytes(
            spec, sizes["experts_hit"], sizes["pairs"],
            sizes["layer_calls"])}
    if kernel == "grouped_expert_matmul_prefill":
        return {"bytes": grouped_expert_matmul_bytes(
            spec, sizes["prefill_experts_hit"], sizes["prefill_pairs"],
            sizes["prefill_layer_calls"])}
    raise KeyError(f"sdar counts no kernel named {kernel!r}")


# ----------------------------------------------------------------- program
def program_kwargs(spec: dict, deployment: dict | None = None) -> dict:
    """Keywords of the program's config object. ``denoising_steps`` is
    the cell's where its ``deployment`` states one, else the
    configuration's."""
    steps = (deployment or {}).get("denoising_steps",
                                   spec["denoising_steps"])
    return dict(
        vocab_size=spec["vocab_size"], hidden=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"], head_dim=spec["head_dim"],
        rope_theta=float(spec["rope_theta"]),
        expert_dim=spec["moe_intermediate_size"],
        n_experts=router_width(spec), top_k=spec["num_experts_per_tok"],
        experts_held=(spec.get("experts_first", 0), spec["num_experts"]),
        norm_eps=spec["rms_norm_eps"],
        max_seq=spec["max_position_embeddings"],
        block_length=spec["block_length"], denoising_steps=steps,
        mask_token_id=spec["mask_token_id"], remasking=spec["remasking"])


def program_config(spec: dict, deployment: dict | None = None):
    from ray_tpu.models import sdar

    return sdar.SdarConfig(**program_kwargs(spec, deployment))


def engine_kwargs(spec: dict, deployment: dict) -> dict:
    """Keyword arguments of ``LLMEngine`` but the weights: the engine
    takes the block's length, the steps a block and the mask id from the
    config object, and has no keyword for them."""
    return dict(config=program_config(spec, deployment), seed=0,
                num_slots=deployment["num_slots"],
                max_seq=deployment["max_seq"], kv_cache="paged",
                kv_pool_tokens=deployment["kv_pool_tokens"],
                kv_block_size=deployment["kv_block_size"],
                prefix_cache="off")


# ----------------------------------------------------------------- weights
def weight_shapes(spec: dict) -> dict:
    """The tree the program's builders take: ``layers`` a LIST, one dict
    a layer; a norm's stored ``w`` scales by ``1 + w`` (the head norms
    too); a layer holds its router and the held experts' matrices
    stacked on a leading axis."""
    h, D = spec["hidden_size"], spec["head_dim"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    m, G = spec["moe_intermediate_size"], spec["num_experts"]
    layer = {"attn_norm": (h,), "wq": (h, H, D), "wk": (h, KV, D),
             "wv": (h, KV, D), "q_norm": (D,), "k_norm": (D,),
             "wo": (H, D, h), "mlp_norm": (h,),
             "router": (h, router_width(spec)),
             "we_gate": (G, h, m), "we_up": (G, h, m), "we_down": (G, m, h)}
    return {"embed": (spec["vocab_size"], h),
            "layers": [dict(layer)
                       for _ in range(spec["num_hidden_layers"])],
            "final_norm": (h,), "lm_head": (h, spec["vocab_size"])}


def weight_stds(spec: dict) -> tuple:
    """Normal draws at ``hidden ** -0.5``; every projection back into
    the residual stream (attention out, the experts' down) scaled down
    by ``sqrt(2 L)`` so that activations stay of order one through the
    depth; norm weights (the head norms among them) at 0.1 so that a
    dropped ``1 + w`` shows; the router at TWICE ``hidden ** -0.5``, so
    that its logits have a standard deviation of 2. Which eight experts
    a token gets does not depend on that scale (nor do the experts hit,
    the loads or a step's bytes); the eight weights do: at 1 the chosen
    are nearly tied (0.24 .. 0.08 after renormalising) and ONE near-tie
    between the eighth and the ninth that bfloat16 activations flip
    moves a row's logits by 0.025-0.03 of their norm, as much as the
    int8 control moves them (PERF.md section 6, PR 48: twelve seeds at
    1); at 2 the eighth expert weighs 0.04 and a flip costs a row a
    third of that. The factor is a DEVICE OF THE CHECK, chosen so that
    the comparison tells bfloat16 from int8; it is no statistic of the
    trained model (no published router weight was read for it)."""
    std = spec["hidden_size"] ** -0.5
    out_std = std / (2 * spec["num_hidden_layers"]) ** 0.5
    return std, {"attn_norm": 0.1, "mlp_norm": 0.1, "final_norm": 0.1,
                 "q_norm": 0.1, "k_norm": 0.1, "wo": out_std,
                 "we_down": out_std, "router": 2 * std}


# ------------------------------------------------- the check's program side
def _programs(params, spec: dict, deployment: dict, pool_tokens: int):
    """The builders at the deployment's geometry over a pool of
    ``pool_tokens``: (config, page, prefill, block step)."""
    from ray_tpu.models import sdar

    cfg = program_config(spec, deployment)
    page = sdar.make_page(cfg, max_seq=deployment["max_seq"],
                          block_size=deployment["kv_block_size"],
                          pool_tokens=pool_tokens)
    return (cfg, page, sdar.make_prefill(params, cfg, page),
            sdar.make_block_step(params, cfg, page))


def _neighbours(slots: int) -> list:
    """Slots that run beside the compared one (the last): the first, the
    middle and the one before it."""
    return sorted({0, slots // 2, slots - 2} & set(range(slots - 1)))


def engine_block_steps(engine, spec: dict, tokens, prefill: int):
    """The check's steps on an idle ``engine``'s OWN programs, cache,
    tables and allocator: ``_prefill``, ``_block_step``,
    ``_block_decide`` and ``_seat_blocks``, as ``_dispatch_block`` calls
    them. The sequence sits in the LAST slot; up to three other slots
    (``_neighbours``) hold shorter prompts of other tokens and run in
    every step beside it, each at another point of its block (nothing
    decided, half decided, all decided: that one commits in the first
    step), and go on from what the deciding program leaves on the
    device. After the block-causal prefill of ``tokens[:prefill]`` every
    block of ``tokens`` takes two steps of the last slot:

    - a DENOISING step: the block seated with some positions decided
      (every other one; in every second block none) and the rest fed
      ``mask_token_id``, its logits against ONE forward of the reference
      over ``tokens`` with the block replaced by what the step was fed;
    - the COMMIT: the block seated again on the given ids, every
      position decided; its logits are the rows returned, and the rows
      it stores are what the next block attends over.

    In every step the deciding program's ids, flags and committed ids
    of every running slot are held to ``reference.decide`` on the
    step's own logits (RuntimeError where they differ: no tolerance
    applies). -> (rows (1 + blocks x block_length, vocab) float32 as
    ``serve_program_logits`` returns them, the denoising steps' rel_err
    over all their rows)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import model_spec

    ref = model_spec.reference(spec)
    cfg, slots, params = engine.config, engine.num_slots, engine.params
    B = cfg.block_length
    toks = np.asarray(tokens)
    if B < 2 or prefill % B or (len(toks) - prefill) % B:
        raise RuntimeError(f"the check's {prefill} + {len(toks) - prefill} "
                           f"tokens are no whole blocks of {B} (of two "
                           "positions or more: one is left undecided)")
    if (engine._flight is not None or engine.stats()["queued"]
            or any(r is not None for r in engine._slots)):
        raise RuntimeError("the check borrows an idle engine; this one "
                           "is not")
    alloc, cache = engine._alloc, engine._cache
    last = slots - 1
    # slot -> [its tokens, the rows it has committed]; the compared slot
    # is prefilled last, so ``lg`` below is its
    seqs = {slot: [np.roll(toks, 17 * (j + 1)),
                   max(B, prefill // (j + 2) // B * B)]
            for j, slot in enumerate(_neighbours(slots))}
    seqs[last] = [toks, prefill]
    run = sorted(seqs)
    active = np.zeros(slots, bool)
    active[run] = True
    ids = np.zeros((slots, B), np.int32)        # the host's copy of the
    decided = np.zeros((slots, B), bool)        # blocks on the device
    begun = np.full(slots, B)           # undecided when its block began
    for j, slot in enumerate(run[:-1]):
        seq, n = seqs[slot]
        tail = (0, B // 2, B)[j % 3]
        ids[slot, :tail] = seq[n:n + tail]
        decided[slot, :tail] = True
        begun[slot] = B - tail
    state = (jnp.zeros((slots, B), jnp.int32), jnp.zeros((slots, B), bool))
    seated = active.copy()
    rows, denoised, denoised_ref = [], [], []
    try:
        for slot in run:
            seq, n = seqs[slot]
            if not alloc.ensure(slot, n + B):
                raise RuntimeError("the pool is too small for the check")
            padded = np.zeros((1, engine._prompt_pad(n)), np.int32)
            padded[0, :n] = seq[:n]
            cache, lg = engine._prefill(cache, alloc.table_rows(slot),
                                        jnp.asarray(padded), n, slot)
            cache = dict(cache, counters=None)
        rows.append(np.asarray(lg, np.float32).reshape(1, -1))
        for start in range(prefill, len(toks), B):
            given = toks[start:start + B]
            some = ((np.arange(B) % 2 == 0) if (start - prefill) // B % 2 == 0
                    else np.zeros(B, bool))
            for flags in (some, np.ones(B, bool)):      # denoise, commit
                ids[last], decided[last] = np.where(flags, given, 0), flags
                begun[last] = B - flags.sum()
                state = engine._seat_blocks(
                    *state, jnp.asarray(ids.copy()),
                    jnp.asarray(decided.copy()), jnp.asarray(seated))
                seated = np.arange(slots) == last
                for slot in run:
                    if not alloc.ensure(slot, seqs[slot][1] + B):
                        raise RuntimeError("the pool is too small for the "
                                           "check")
                commits = active & decided.all(axis=1)
                quota = np.where(
                    active & ~commits,
                    np.minimum([cfg.step_quota(int(u)) for u in begun],
                               (~decided).sum(axis=1)), 0).astype(np.int32)
                cache, logits = engine._block_step(
                    cache, alloc.device_tables(), *state,
                    jnp.asarray(active))
                cache = dict(cache, counters=None)
                *state, out = engine._block_decide(
                    logits, *state, jnp.asarray(quota), None)
                lg = np.asarray(logits[np.asarray(run)], np.float32)
                got = [np.asarray(a) for a in (*state, out)]
                for i, slot in enumerate(run):
                    if commits[slot]:
                        want = (ids[slot], np.zeros(B, bool), ids[slot])
                        seqs[slot][1] += B
                        begun[slot] = B
                    else:
                        picked, now = ref.decide(lg[i], decided[slot],
                                                 int(quota[slot]))
                        want = (np.where(now, picked, ids[slot]),
                                decided[slot] | now, np.zeros(B, np.int32))
                    if any((g[slot] != w).any() for g, w in zip(got, want)):
                        raise RuntimeError(
                            f"the deciding program left slot {slot} (ids, "
                            f"decided, committed) "
                            f"{[g[slot].tolist() for g in got]}; the "
                            "reference's rule on the same logits gives "
                            f"{[np.asarray(w).tolist() for w in want]}")
                    ids[slot], decided[slot] = want[0], want[1]
                if flags.all():
                    rows.append(lg[-1])
                    continue
                denoised.append(lg[-1])
                fed = np.where(flags, given, spec["mask_token_id"])
                # the later tokens stay (no row of this block sees them):
                # the reference then compiles for one length only
                denoised_ref.append(np.asarray(ref.logits(
                    params, np.concatenate([toks[:start], fed,
                                            toks[start + B:]]), spec,
                    rows=list(range(start, start + B)), quiet=True)))
    finally:
        for slot in run:
            alloc.release(slot)
        engine._cache = cache
    return (np.concatenate(rows),
            ref.rel_err(np.concatenate(denoised),
                        np.concatenate(denoised_ref)))


def serve_program_logits(params, spec: dict, tokens, deployment: dict, *,
                         prefill: int, engine=None):
    """Row ``prefill - 1`` from the block-causal prefill of the first
    ``prefill`` tokens (a whole number of blocks), then the rows after
    it from the commit steps of the blocks that follow, by
    :func:`engine_block_steps` on the ENGINE's own programs, pool and
    tables: the replica's idle ``engine`` where the caller hands it over
    (a run of the cell: ``worker_serve.check``), else an engine of the
    deployment built here and shut down again (``control.py``, the
    tests), so the limits are read on the path they judge.

    ``checks.py`` compares the returned rows with the reference's one
    forward of ``tokens``. A denoising step is fed other ids than
    ``tokens`` (the mask id where a position is undecided), so its
    comparison cannot travel in those rows: it is made here, printed,
    and held to ``serve_denoise_logits_rel_err`` of the configuration's
    limits file (a limit of its own, set from its own two readings: rows
    that are fed the same id err together, so it reads higher than the
    commit rows; where a limits file has none, the decode limit). A
    fault of it, or of the deciding program, raises: the run then prints
    no result."""
    from benchmark import model_spec

    own = engine is None
    if own:
        from ray_tpu.serve.llm import LLMEngine

        engine = LLMEngine(params=params, **engine_kwargs(spec, deployment))
    elif engine.params is not params:
        raise RuntimeError("the engine handed over serves other weights")
    try:
        rows, value = engine_block_steps(engine, spec, tokens, prefill)
    finally:
        if own:
            engine.shutdown()
    lim = model_spec.limits(spec)
    limit = lim.get("serve_denoise_logits_rel_err",
                    lim["serve_decode_logits_rel_err"])["limit"]
    print(f"read serve_denoise_logits_rel_err {value} (limit {limit}, "
          "judged here)", flush=True)
    if not value <= limit:
        raise RuntimeError(
            f"the denoising steps' logits are {value} off the reference's "
            f"forward of what they were fed; the limit is {limit}")
    return rows


def train_program_loss_and_grads(params, spec: dict, tokens, rules=None):
    raise SystemExit(NO_TRAIN)


# ------------------------------------------------- programs from shapes alone
def lower_serve_programs(spec: dict, deployment: dict, device):
    """(block step, bucket -> prefill) lowered for one device: the block
    step stands where the other adapters' decode step does."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.models import sdar

    from benchmark import weights
    from benchmark.sizing import on, sds

    one = SingleDeviceSharding(device)
    slots, B = deployment["num_slots"], spec["block_length"]
    params = on(one, jax.eval_shape(
        weights.init_fn(spec), jax.eval_shape(lambda: jax.random.key(0))))
    cfg, page, prefill, step = _programs(params, spec, deployment,
                                         deployment["kv_pool_tokens"])
    cache = on(one, jax.eval_shape(
        lambda: sdar.init_cache(cfg, page, slots)))
    mbs = page.max_blocks_per_seq
    block_step = step.jitted.lower(
        params, cache, sds((slots, mbs), jnp.int32, one),
        sds((slots, B), jnp.int32, one), sds((slots, B), jnp.bool_, one),
        sds((slots,), jnp.bool_, one))

    def bucket(pad_len):
        return prefill.jitted.lower(
            params, cache, sds((mbs,), jnp.int32, one),
            sds((1, pad_len), jnp.int32, one), sds((), jnp.int32, one),
            sds((), jnp.int32, one), pad_len=pad_len)

    return block_step, bucket


def lower_decide(spec: dict, deployment: dict, device):
    """The deciding program lowered for one device (greedy: no draw)."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.models import sdar

    from benchmark.sizing import sds

    one = SingleDeviceSharding(device)
    slots, B = deployment["num_slots"], spec["block_length"]
    return sdar.make_decide(program_config(spec, deployment)).lower(
        sds((slots, B, spec["vocab_size"]), jnp.float32, one),
        sds((slots, B), jnp.int32, one), sds((slots, B), jnp.bool_, one),
        sds((slots,), jnp.int32, one))


def train_setup(spec: dict, job: dict, mesh):
    raise SystemExit(NO_TRAIN)
