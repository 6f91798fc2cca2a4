"""The adapter of the looped decoder (``"architecture": "ouro"``,
``model_type`` ``ouro``): a dense block (16 MHA heads of 128, rotary,
SwiGLU, no bias, an untied head) between FOUR RMSNorms, whose ``L``
layers run ``T = total_ut_steps`` times a token, the normed output of
one pass the input of the next, with an exit gate after every pass. The
program's side is ``ray_tpu.models.ouro`` on the paged serving path; the
reference is ``benchmark/reference/ouro.py``, whose docstring has the
equations.

What a reader of the numbers needs:

- **The pool is ``T * L`` layers deep under ``L`` layers of weights.**
  Keys and values of pass ``t``, layer ``l`` are rows of their own at
  pool index ``t * L + l``; ``kv_bytes_per_token`` is ONE pool layer's,
  as the other adapters count it, and ``pool_layers(spec)`` says how
  many there are (192 published: 1.5 MiB a cached token).
- **``kernel_counts`` is ONE of the ``T * L`` kernel calls of a step;
  ``decode_step_bytes`` is the whole step**: ``T`` reads of the layers'
  weights, the head, the embedding's rows, every live row of every pool
  layer once, the rows written, the logits.
- **The check borrows the replica's engine** (``serve_program_logits``'s
  ``engine=``): weights of 4.97 GiB and a pool of 9 GiB leave no room
  for a scratch pool beside them, so the comparison runs the engine's
  own compiled prefill and decode step on its own pool while it is idle,
  and gives the slots back.

Importing it imports no jax. The contract is the table in
``benchmark/README.md``.
"""

from __future__ import annotations

KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "max_position_embeddings", "rms_norm_eps", "rope_theta", "rope_scaling",
    "tie_word_embeddings", "use_sliding_window", "hidden_act",
    "total_ut_steps", "early_exit_threshold")
PUBLISHED_PARAMS = 2.67e9           # the family states 2.6B
NO_TRAIN = ("the ouro block has no train path: no train cell runs it (the "
            "family trains the loop under the loss expected over the exit "
            "distribution with an entropy term, which the train path has "
            "not; at 16 bytes a parameter one chip holds 9 of its 48 "
            "layers)")


def check_config(spec: dict) -> None:
    """The program's side of the block, the keys it needs, and the count
    of the published keys: a checkout whose program lacks the module (a
    commit from before the block was added) exits here, in the driver,
    before any process is started."""
    import os

    name = spec.get("name")
    program = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "ray_tpu", "models", "ouro.py")
    if not os.path.isfile(program):
        raise SystemExit(f"config {name!r}: this checkout's program cannot "
                         f"run the ouro block: no file {program}")
    missing = sorted(k for k in KEYS if k not in spec)
    if missing:
        raise SystemExit(f"config {name!r}: the ouro block needs the keys "
                         f"{missing}")
    if (spec["tie_word_embeddings"] or spec["use_sliding_window"]
            or spec["rope_scaling"] or spec["hidden_act"] != "silu"
            or spec["total_ut_steps"] < 1
            or not 0.0 < spec["early_exit_threshold"] <= 1.0):
        raise SystemExit(
            f"config {name!r}: the ouro block has an untied head, no "
            "window, plain rotary and SwiGLU, runs its layers once at "
            "least and leaves at a cumulated probability in (0, 1]")
    if "published" in spec:
        whole = num_params(spec["published"])
        if abs(whole / PUBLISHED_PARAMS - 1.0) > 1e-2:
            raise SystemExit(f"config {name!r}: the published keys count "
                             f"{whole / 1e9:.3f} B parameters, not 2.67 B")


# ------------------------------------------------------------------ counts
def _layers(spec, layers):
    return spec["num_hidden_layers"] if layers is None else layers


def pool_layers(spec: dict) -> int:
    """Layers of KV state: one for each pass of each layer."""
    return spec["total_ut_steps"] * spec["num_hidden_layers"]


def matrix_params(spec: dict, layers: int | None = None) -> dict:
    """Stored parameters that take part in a matrix multiply, by group."""
    h, m = spec["hidden_size"], spec["intermediate_size"]
    q = spec["num_attention_heads"] * spec["head_dim"]
    kv = spec["num_key_value_heads"] * spec["head_dim"]
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * m
    return {"per_layer": per_layer,
            "layers": _layers(spec, layers) * per_layer,
            "head": h * spec["vocab_size"]}


def num_params(spec: dict, layers: int | None = None) -> int:
    """All stored parameters: embedding, head, the layers' matrices and
    four norms each, the final norm, the gate's vector and its bias."""
    h = spec["hidden_size"]
    mp = matrix_params(spec, layers)
    return (spec["vocab_size"] * h + mp["head"] + mp["layers"]
            + _layers(spec, layers) * 4 * h + 2 * h + 1)


def train_flops_per_token(spec: dict, seq: int) -> float:
    raise SystemExit(NO_TRAIN)


def kv_bytes_per_token(spec: dict) -> int:
    """Bytes of keys and values ONE pool layer keeps for one cached token
    (bf16), as the dense adapter counts it; a token costs
    ``pool_layers(spec)`` times this."""
    return 2 * spec["num_key_value_heads"] * spec["head_dim"] * 2


def paged_decode_bytes(spec: dict, live_tokens: float, slots: float
                       ) -> float:
    """Bytes ONE call of the paged decode kernel (one pass of one layer)
    has to move: that pool layer's live keys and values once, the queries
    in and the outputs out (bf16)."""
    q = spec["num_attention_heads"] * spec["head_dim"]
    return live_tokens * kv_bytes_per_token(spec) + 2 * slots * q * 2


def decode_step_bytes(spec: dict, live_tokens: float, slots: float
                      ) -> float:
    """Bytes a WHOLE decode step has to move: the layers' weights (their
    matrices and norms, bf16) once a pass, the final norm and the gate a
    pass, the head once, the embedding's rows, every live row of all
    ``T * L`` pool layers once, the rows written, the logits out
    (float32). Activations between operations are left out: it errs
    low."""
    T, h = spec["total_ut_steps"], spec["hidden_size"]
    layers = 2 * (matrix_params(spec)["layers"]
                  + spec["num_hidden_layers"] * 4 * h)
    cache = pool_layers(spec) * kv_bytes_per_token(spec)
    return (T * (layers + 2 * (2 * h + 1))
            + 2 * h * spec["vocab_size"] + slots * h * 2
            + live_tokens * cache + slots * cache
            + slots * spec["vocab_size"] * 4)


def kernel_counts(spec: dict, kernel: str, **sizes) -> dict:
    """Bytes of ONE call of the kernel whose custom call carries this
    instruction name: here one of the ``T * L`` calls of a step.
    ``sizes``: ``live_tokens`` (the allocator's), ``slots`` (the
    cell's)."""
    if kernel == "paged_decode_attention":
        return {"bytes": paged_decode_bytes(spec, sizes["live_tokens"],
                                            sizes["slots"])}
    raise KeyError(f"ouro counts no kernel named {kernel!r}")


# ----------------------------------------------------------------- program
def program_kwargs(spec: dict) -> dict:
    """Keywords of the program's config object."""
    return dict(
        vocab_size=spec["vocab_size"], hidden=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"], head_dim=spec["head_dim"],
        mlp_dim=spec["intermediate_size"],
        max_seq=spec["max_position_embeddings"],
        rope_theta=float(spec["rope_theta"]), norm_eps=spec["rms_norm_eps"],
        total_ut_steps=spec["total_ut_steps"],
        early_exit_threshold=float(spec["early_exit_threshold"]))


def program_config(spec: dict):
    from ray_tpu.models import ouro

    return ouro.OuroConfig(**program_kwargs(spec))


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
    """The tree the program's builders take: ``layers`` stacked on a
    leading axis; a norm's stored ``w`` scales by ``1 + w``; the gate is
    a vector ``exit_w`` and a bias ``exit_b``."""
    L, h, m = (spec["num_hidden_layers"], spec["hidden_size"],
               spec["intermediate_size"])
    H, KV, D = (spec["num_attention_heads"], spec["num_key_value_heads"],
                spec["head_dim"])
    return {"embed": (spec["vocab_size"], h),
            "layers": {
                "attn_norm": (L, h), "wq": (L, h, H, D), "wk": (L, h, KV, D),
                "wv": (L, h, KV, D), "wo": (L, H, D, h),
                "attn_post_norm": (L, h), "mlp_norm": (L, h),
                "w_gate": (L, h, m), "w_up": (L, h, m), "w_down": (L, m, h),
                "mlp_post_norm": (L, h)},
            "final_norm": (h,), "exit_w": (h,), "exit_b": (1,),
            "lm_head": (h, spec["vocab_size"])}


LOOP_SCALE = 6.0


def weight_stds(spec: dict) -> tuple:
    """Normal draws at ``hidden ** -0.5``; the norms inside a block at
    0.1 so that a dropped ``1 + w`` shows. The projections back into the
    stream are NOT scaled down by depth, as the dense adapter's are: a
    norm stands after each sublayer, so a sublayer adds a vector of unit
    scale whatever its last matrix's.

    The embedding and the final norm are drawn at ``LOOP_SCALE`` = 6, so
    that every pass STARTS from a state six times the scale of what one
    sublayer adds (and the gate's vector at ``hidden ** -0.5 / 6``, so
    that its logits keep a standard deviation of about 1 and the exit
    distribution differs by position and by pass; the bias at 0.5). At 1
    the seeded loop (48 layers, then the norm, applied four times with
    the same weights) contracts on some seeds and EXPANDS on others:
    bfloat16's rounding then grows from pass to pass (0.020, 0.030,
    0.051, 0.17 of the logits' norm after 1, 2, 3, 4 passes on one seed
    of twelve, 0.0096 on seven of them) while the int8 control saturates
    near 1, and no limit has room on both sides (PERF.md section 6, PR
    52). At 6 twenty-nine seeds read 0.016-0.035 and the control
    0.38-0.93 on twelve.
    The factor is a DEVICE OF THE CHECK, chosen so that the comparison
    tells bfloat16 from int8 on every seed; it is no statistic of the
    trained model (no published weight was read for it), and no time,
    byte or count of a run depends on it."""
    std = spec["hidden_size"] ** -0.5
    return std, {
        "attn_norm": 0.1, "attn_post_norm": 0.1, "mlp_norm": 0.1,
        "mlp_post_norm": 0.1, "final_norm": LOOP_SCALE,
        "embed": LOOP_SCALE, "exit_w": std / LOOP_SCALE, "exit_b": 0.5}


# ------------------------------------------------- the check's program side
def _neighbours(slots: int) -> list:
    """Slots that run beside the compared one (the last): the first, the
    middle and the one before it."""
    return sorted({0, slots // 2, slots - 2} & set(range(slots - 1)))


def engine_steps(engine, tokens, prefill: int):
    """The check's steps on an idle ``engine``'s OWN programs, cache,
    tables and allocator, as ``LLMEngine`` calls them: the sequence in
    the LAST slot, up to three other slots (``_neighbours``) with
    shorter prompts of other tokens that run in every step beside it.
    Prefill of ``tokens[:prefill]``, then one teacher-forced decode step
    for each token after them. The slots are released afterwards. ->
    (1 + steps, vocab) float32."""
    import jax.numpy as jnp
    import numpy as np

    slots = engine.num_slots
    if (engine._flight is not None or engine.stats()["queued"]
            or any(r is not None for r in engine._slots)):
        raise RuntimeError("the check borrows an idle engine; this one is "
                           "not")
    alloc = engine._alloc
    # a program's counters are its output alone (the engine's cache
    # holds None there, so this is the signature the engine compiles)
    cache = dict(engine._cache, counters=None)
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
            padded = np.zeros((1, engine._prompt_pad(n)), np.int32)
            padded[0, :n] = seq[:n]
            cache, lg = engine._prefill(cache, alloc.table_rows(slot),
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
            cache, lg = engine._decode(cache, alloc.device_tables(),
                                       jnp.asarray(fed), jnp.asarray(active))
            cache = dict(cache, counters=None)
            rows.append(np.asarray(lg, np.float32)[last])
    finally:
        for slot in seqs:
            alloc.release(slot)
        engine._cache = cache
    return np.stack(rows)


def serve_program_logits(params, spec: dict, tokens, deployment: dict, *,
                         prefill: int, engine=None):
    """Logits at positions ``prefill - 1 .. len(tokens) - 1`` by
    :func:`engine_steps` on the ENGINE's own programs, pool and tables:
    the replica's idle ``engine`` where the caller hands it over (a run
    of the cell: ``worker_serve.check``), else an engine of the
    deployment built here and shut down again (``control.py``, the
    tests), so the limits are read on the path they judge. No scratch
    pool is ever built beside an engine: at the published sizes it does
    not fit."""
    own = engine is None
    if own:
        from ray_tpu.serve.llm import LLMEngine

        engine = LLMEngine(params=params, **engine_kwargs(spec, deployment))
    elif engine.params is not params:
        raise RuntimeError("the engine handed over serves other weights")
    try:
        return engine_steps(engine, tokens, prefill)
    finally:
        if own:
            engine.shutdown()


def train_program_loss_and_grads(params, spec: dict, tokens, rules=None):
    raise SystemExit(NO_TRAIN)


# ------------------------------------------------- programs from shapes alone
def lower_serve_programs(spec: dict, deployment: dict, device):
    """(decode step, bucket -> prefill) lowered for one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.models import ouro

    from benchmark import weights
    from benchmark.sizing import on, sds

    one = SingleDeviceSharding(device)
    cfg = program_config(spec)
    slots = deployment["num_slots"]
    page = ouro.make_page(max_seq=deployment["max_seq"],
                          block_size=deployment["kv_block_size"],
                          pool_tokens=deployment["kv_pool_tokens"])
    params = on(one, jax.eval_shape(
        weights.init_fn(spec), jax.eval_shape(lambda: jax.random.key(0))))
    cache = on(one, jax.eval_shape(     # counters None: as the engine calls
        lambda: dict(ouro.init_cache(cfg, page, slots), counters=None)))
    mbs = page.max_blocks_per_seq
    decode = ouro.make_decode_step(params, cfg, page).jitted.lower(
        params, cache, sds((slots, mbs), jnp.int32, one),
        sds((slots,), jnp.int32, one), sds((slots,), jnp.bool_, one))
    prefill = ouro.make_prefill(params, cfg, page)

    def bucket(pad_len):
        return prefill.jitted.lower(
            params, cache, sds((mbs,), jnp.int32, one),
            sds((1, pad_len), jnp.int32, one), sds((), jnp.int32, one),
            sds((), jnp.int32, one), pad_len=pad_len)

    return decode, bucket


def train_setup(spec: dict, job: dict, mesh):
    raise SystemExit(NO_TRAIN)
