"""The adapter of the dense decoder block (``"architecture":
"dense_decoder"``): everything the harness knows about this block, found
by the configuration's key (``model_spec.adapter``). Pre-norm RMSNorm,
rotary grouped-query attention, SwiGLU, untied head; the program's side
is ``ray_tpu.models.llama`` with the paged serving builders.

Importing it imports no jax: the driver process reads it. What needs jax
or the program imports it inside the function. The contract every
adapter keeps is the table in ``benchmark/README.md``.

Sizes, parameter counts and required operations are computed from the
published keys of ``benchmark/configs/<config>.json``; nothing is asked
of the program (its ``LlamaConfig.flops_per_token`` counts the embedding
gather as a matrix multiply, which it is not).
"""

from __future__ import annotations

# published key -> field of the program's LlamaConfig. Only these are set;
# every other field keeps the program's default, so a later PR that
# changes a default is measured.
PROGRAM_FIELDS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "mlp_dim",
    "max_position_embeddings": "max_seq",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def check_config(spec: dict) -> None:
    missing = sorted(k for k in PROGRAM_FIELDS if k not in spec)
    if missing:
        raise SystemExit(f"config {spec.get('name')!r}: the dense_decoder "
                         f"block needs the keys {missing}")


# ------------------------------------------------------------------ counts
def matrix_params(spec: dict, layers: int | None = None) -> dict:
    """Parameters that take part in a matrix multiply, by group."""
    h, m = spec["hidden_size"], spec["intermediate_size"]
    q = spec["num_attention_heads"] * spec["head_dim"]
    kv = spec["num_key_value_heads"] * spec["head_dim"]
    n = spec["num_hidden_layers"] if layers is None else layers
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * m
    return {"per_layer": per_layer, "layers": n * per_layer,
            "head": h * spec["vocab_size"]}


def num_params(spec: dict, layers: int | None = None) -> int:
    """All stored parameters: embedding table, blocks with their two
    norms, final norm, and the head where it is not tied."""
    h, v = spec["hidden_size"], spec["vocab_size"]
    n = spec["num_hidden_layers"] if layers is None else layers
    mp = matrix_params(spec, layers)
    total = v * h + mp["layers"] + n * 2 * h + h
    if not spec["tie_word_embeddings"]:
        total += mp["head"]
    return total


def train_flops_per_token(spec: dict, seq: int) -> float:
    """Operations the forward and backward passes REQUIRE for one trained
    token: 6 for every parameter in a matrix multiply (2 forward, 4
    backward), none for the embedding gather, none for recomputation,
    plus causal attention: forward QK^T and PV are 4*S*d over the full
    square, halved by causality, and the backward costs twice the
    forward: 3 * 2*S*d = 6*S*d a layer (d = heads * head size)."""
    mp = matrix_params(spec)
    q = spec["num_attention_heads"] * spec["head_dim"]
    return (6.0 * (mp["layers"] + mp["head"])
            + 6.0 * spec["num_hidden_layers"] * seq * q)


def flash_flops(spec: dict, batch: int, seq: int) -> dict:
    """Operations of ONE call of each causal flash kernel (one layer, one
    step), counted over the lower triangle. Forward: QK^T and PV. The
    backward is split in two kernels that each recompute what they need
    (flash attention stores no scores): dq = scores, dP, dQ; dkv =
    scores, dP, dV, dK."""
    h, d = spec["num_attention_heads"], spec["head_dim"]
    tri = batch * h * seq * seq * d      # one matmul over half the square
    return {"fwd": 2 * tri, "bwd_dq": 3 * tri, "bwd_dkv": 4 * tri}


def kv_bytes_per_token(spec: dict) -> int:
    """Bytes of keys and values one cached token takes in ONE layer."""
    return 2 * spec["num_key_value_heads"] * spec["head_dim"] * 2


def paged_decode_bytes(spec: dict, live_tokens: int, slots: int) -> int:
    """Bytes the paged decode-attention kernel has to move for ONE layer
    and one step: the live keys and values once, the queries in and the
    outputs out (bf16)."""
    q = spec["num_attention_heads"] * spec["head_dim"]
    return live_tokens * kv_bytes_per_token(spec) + 2 * slots * q * 2


_FLASH = {"flash_attention_fwd": "fwd", "flash_attention_dq": "bwd_dq",
          "flash_attention_dkv": "bwd_dkv"}


def kernel_counts(spec: dict, kernel: str, **sizes) -> dict:
    """Operations and bytes of ONE call of the kernel whose custom call
    carries this instruction name (the program's ``pallas_call(name=)``);
    the side a roofline does not bound on is left out. ``sizes`` are the
    cell's: ``batch``, ``seq`` for the flash kernels, ``live_tokens``,
    ``slots`` for paged decode."""
    if kernel in _FLASH:
        return {"flops": flash_flops(spec, sizes["batch"],
                                     sizes["seq"])[_FLASH[kernel]]}
    if kernel == "paged_decode_attention":
        return {"bytes": paged_decode_bytes(spec, sizes["live_tokens"],
                                            sizes["slots"])}
    raise KeyError(f"dense_decoder counts no kernel named {kernel!r}")


# ----------------------------------------------------------------- program
def program_kwargs(spec: dict) -> dict:
    """Keyword arguments for the program's config class."""
    return {field: spec[key] for key, field in PROGRAM_FIELDS.items()}


def program_config(spec: dict):
    from ray_tpu.models import llama

    return llama.LlamaConfig(**program_kwargs(spec))


def engine_kwargs(spec: dict, deployment: dict) -> dict:
    """Keyword arguments of ``LLMEngine`` but the weights: the cell
    file's deployment figures this block's engine takes."""
    return dict(config=program_config(spec), seed=0,
                num_slots=deployment["num_slots"],
                max_seq=deployment["max_seq"], kv_cache="paged",
                kv_pool_tokens=deployment["kv_pool_tokens"],
                kv_block_size=deployment["kv_block_size"],
                prefix_cache="off")


# ----------------------------------------------------------------- weights
def weight_shapes(spec: dict) -> dict:
    """The tree in the layout the program's builders take: ``embed``,
    ``layers`` stacked on a leading axis, ``final_norm``, ``lm_head``; a
    norm's stored weight ``w`` scales by ``1 + w``."""
    L, h, m = (spec["num_hidden_layers"], spec["hidden_size"],
               spec["intermediate_size"])
    H, KV, D = (spec["num_attention_heads"], spec["num_key_value_heads"],
                spec["head_dim"])
    out = {
        "embed": (spec["vocab_size"], h),
        "layers": {
            "attn_norm": (L, h), "wq": (L, h, H, D), "wk": (L, h, KV, D),
            "wv": (L, h, KV, D), "wo": (L, H, D, h), "mlp_norm": (L, h),
            "w_gate": (L, h, m), "w_up": (L, h, m), "w_down": (L, m, h),
        },
        "final_norm": (h,),
    }
    if not spec["tie_word_embeddings"]:
        out["lm_head"] = (h, spec["vocab_size"])
    return out


def weight_stds(spec: dict) -> tuple:
    """(the draws' standard deviation, {leaf name: its own}). Normal
    draws at ``hidden ** -0.5``; projections back into the residual
    stream are scaled down by ``sqrt(2 L)`` so that activations stay of
    order one through the depth; norm weights are drawn at 0.1 so that a
    dropped ``1 + w`` shows."""
    std = spec["hidden_size"] ** -0.5
    out_std = std / (2 * spec["num_hidden_layers"]) ** 0.5
    return std, {"attn_norm": 0.1, "mlp_norm": 0.1, "final_norm": 0.1,
                 "wo": out_std, "w_down": out_std}


# ------------------------------------------------- the check's program side
def serve_program_logits(params, spec: dict, tokens, deployment: dict, *,
                         prefill: int):
    """Prefill of the first ``prefill`` tokens, then one teacher-forced
    decode step for each token after them through a scratch pool, with
    the builders the engine uses at the engine's slot count, ``max_seq``
    and block size. -> (1 + steps, vocab) float32: the logits at
    positions ``prefill - 1 .. len(tokens) - 1``."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.paged_cache import (
        BlockAllocator, PagedConfig, init_paged_cache,
        make_paged_decode_step, make_paged_prefill, pad_to_block_bucket)

    cfg = program_config(spec)
    num_slots, block_size = deployment["num_slots"], deployment["kv_block_size"]
    toks = np.asarray(tokens)
    total = len(toks)
    page = PagedConfig(num_blocks=2 + -(-(total + 1) // block_size),
                       block_size=block_size, max_seq=deployment["max_seq"])
    alloc = BlockAllocator(page, num_slots)
    cache = init_paged_cache(cfg, page, num_slots)
    prefill_fn = make_paged_prefill(params, cfg, page)
    decode = make_paged_decode_step(params, cfg, page)
    slot = num_slots - 1                  # not the first: indexing shows
    if not alloc.ensure(slot, total + 1):
        raise RuntimeError("the scratch pool is too small for the check")
    P = pad_to_block_bucket(prefill, block_size)
    padded = np.zeros((1, P), np.int32)
    padded[0, :prefill] = toks[:prefill]
    cache, lg = prefill_fn(cache, alloc.tables[slot], jnp.asarray(padded),
                           prefill, slot)
    rows = [np.asarray(lg, np.float32).reshape(-1)]
    active = np.zeros(num_slots, bool)
    active[slot] = True
    for i in range(total - prefill):
        last = np.zeros(num_slots, np.int32)
        last[slot] = toks[prefill + i]
        cache, lg = decode(cache, alloc.device_tables(), jnp.asarray(last),
                           jnp.asarray(active))
        rows.append(np.asarray(lg, np.float32)[slot])
    return np.stack(rows)


def train_program_loss_and_grads(params, spec: dict, tokens, rules=None):
    """The program's loss and gradients on one sequence, through the
    code the train step differentiates (``llama.loss_fn``: flash forward
    and backward kernels, remat scan). Returns the loss and the
    gradients of the last block, the final norm and the head."""
    import jax
    from ray_tpu.models import llama

    cfg = program_config(spec)
    L = spec["num_hidden_layers"]

    def f(p, toks):
        return llama.loss_fn(p, {"tokens": toks[None, :]}, cfg, rules)[0]

    def tail_of(p, toks):
        loss, g = jax.value_and_grad(f)(p, toks)
        return loss, {"layer": jax.tree.map(lambda a: a[L - 1], g["layers"]),
                      "final_norm": g["final_norm"],
                      "lm_head": g["lm_head"]}

    return jax.jit(tail_of)(params, tokens)


# ------------------------------------------------- programs from shapes alone
def lower_serve_programs(spec: dict, deployment: dict, device):
    """(decode step, bucket -> prefill) lowered for one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.models.paged_cache import (
        PagedConfig, init_paged_cache, make_paged_decode_step,
        make_paged_prefill)

    from benchmark import weights
    from benchmark.sizing import on, sds

    one = SingleDeviceSharding(device)
    cfg = program_config(spec)
    slots, bs = deployment["num_slots"], deployment["kv_block_size"]
    page = PagedConfig(
        num_blocks=1 + -(-deployment["kv_pool_tokens"] // bs),
        block_size=bs, max_seq=deployment["max_seq"])
    params = on(one, jax.eval_shape(
        weights.init_fn(spec), jax.eval_shape(lambda: jax.random.key(0))))
    cache = on(one, jax.eval_shape(
        lambda: init_paged_cache(cfg, page, slots)))
    step = make_paged_decode_step(params, cfg, page)
    decode = step.jitted.lower(
        params, cache, sds((slots, page.max_blocks_per_seq), jnp.int32, one),
        sds((slots,), jnp.int32, one), sds((slots,), jnp.bool_, one))
    prefill = make_paged_prefill(params, cfg, page)

    def bucket(pad_len):
        return prefill.jitted.lower(
            params, cache, sds((page.max_blocks_per_seq,), jnp.int32, one),
            sds((1, pad_len), jnp.int32, one), sds((), jnp.int32, one),
            sds((), jnp.int32, one), pad_len=pad_len)

    return decode, bucket


def train_setup(spec: dict, job: dict, mesh):
    """(state shapes with shardings, the jitted step, the rules,
    ``key -> train state`` sharded from birth by the parameters' logical
    axes)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import llama
    from ray_tpu.models.training import (OptimizerConfig, TrainState,
                                         init_train_state, make_train_step,
                                         state_shardings)
    from ray_tpu.parallel.sharding import FSDP_TP_RULES

    from benchmark import weights

    cfg = program_config(spec)
    rules = FSDP_TP_RULES
    axes = llama.param_logical_axes(cfg)
    # the schedule is the traffic mix's (``"optimizer"``: keywords of the
    # program's ``OptimizerConfig``); a caller that only lowers the step
    # from shapes gives none, and the step is the same program
    opt = OptimizerConfig(**job.get("optimizer", {"warmup_steps": 1})).make()
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
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg, rules),
                           opt, mesh, rules)

    def init_state(key):
        return init_train_state(init, axes, opt, mesh, rules, key)[0]

    return state, step, rules, init_state
