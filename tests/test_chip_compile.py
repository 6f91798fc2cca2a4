"""Compile the main path's kernels and the ``1b`` serving programs for a
described (not attached) v5e, at the published widths.

Nothing runs: these tests ask the chip's compiler whether it accepts the
programs, and read what it says about memory. The topology is described
inside a fixture of THIS file and every compile happens in the test's
own process (one process at a time may load the TPU library; see the
on-chip-measurement guide, section 2).
"""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import llama
from ray_tpu.models.paged_cache import (PagedConfig, init_paged_cache,
                                        make_chunked_paged_prefill,
                                        make_paged_decode_step,
                                        make_paged_prefill)

CFG = llama.CONFIGS["1b"]
HBM_BYTES = 16 * 1024 ** 3            # one v5e chip
# a literal as large as the smallest per-layer weight of `tiny`
# (wk: 512 x 4 x 64 bf16 = 256 KiB) would show as >= 512 Ki hex digits
WEIGHT_LITERAL_HEX = 256 * 1024 * 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep these tests silent
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """The dispatchers ask ``jax.default_backend()`` at trace time; here
    it says cpu. Steer them from the test, not through a program option."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding), tree)


def _largest_literal_hex(text: str) -> int:
    return max((len(m) for m in re.findall(r'dense<"0x([0-9A-Fa-f]*)"', text)),
               default=0)


def _nbytes(a) -> int:
    return a.size * a.dtype.itemsize


def _pool_movers(text: str, pool) -> list:
    """Instructions of a compiled module that copy or slice a whole
    layer of the KV pool, or the pool: a ``copy``, ``dynamic-slice`` or
    ``dynamic-update-slice`` (alone or as a fusion named after it) with
    an operand or result of the pool's shape, (L, NB, bs, KV * D), or a
    layer's. The in-place scatter of the new rows is none of these."""
    per_layer = ",".join(str(d) for d in pool.shape[1:])
    shape = re.compile(rf"bf16\[({pool.shape[0]},)?{per_layer}\]")
    mover = re.compile(r"= \S+ (copy|dynamic-slice|dynamic-update-slice)\("
                       r"|^\s*(ROOT )?%\S*(copy|dynamic-slice|"
                       r"dynamic-update-slice)\S* = ")
    return [line.strip()[:200] for line in text.splitlines()
            if mover.search(line) and shape.search(line)]


def _total_bytes(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
            - mem.alias_size_in_bytes)


# ------------------------------------------------------------------ kernels
B, S, H, KV, D = 4, 2048, CFG.n_heads, CFG.n_kv_heads, CFG.head_dim

# (batch, query heads, kv heads, sequence, key width, value width, block
# given, block the call must end up with): the 1b widths at two given
# blocks; the per-device calls of the two train cells and a prompt's full
# layers in the three routed serve cells' largest buckets, whose blocks are
# the kernels' own choice from the shapes
FLASH_CALLS = {
    "1b-512": (B, H, KV, S, D, D, 512, 512),
    "1b-1024": (B, H, KV, S, D, D, 1024, 1024),
    "train-1chip": (6, 16, 16, 4096, 128, 128, None, 1024),
    "train-fsdp2tp2": (8, 16, 4, 4096, 128, 128, None, 1024),
    "mla-prefill-2048": (1, 64, 64, 2048, 192, 128, None, 512),
    "whole-prefill-2048": (1, 48, 8, 2048, 128, 128, None, 1024),
    "window-prefill-1024": (1, 64, 4, 1024, 192, 128, None, 512),
}


@pytest.mark.parametrize("call", FLASH_CALLS)
def test_flash_fwd_compiles(one_chip, call):
    from ray_tpu.ops.pallas.flash_attention import flash_attention_fwd_pallas

    b, hq, hkv, s, dk, dv, block, chosen = FLASH_CALLS[call]
    q = _sds((b, hq, s, dk), jnp.bfloat16, one_chip)
    k = _sds((b, hkv, s, dk), jnp.bfloat16, one_chip)
    v = _sds((b, hkv, s, dv), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention_fwd_pallas(
        q, k, v, causal=True, scale=dk ** -0.5, block_q=block,
        block_kv=block))
    text = fn.lower(q, k, v).compile().as_text()
    assert "tpu_custom_call" in text
    # the work list is the causal triangle of (s / block)^2 block pairs
    n = s // chosen
    pairs = f"s32[{n * (n + 1) // 2}]{{0}}"
    assert f"operand_layout_constraints={{{pairs}, {pairs}, " in text


@pytest.mark.parametrize(
    "call", [c for c, shape in FLASH_CALLS.items() if shape[4] == shape[5]])
def test_flash_bwd_compiles(one_chip, call):
    from ray_tpu.ops.pallas.flash_attention import flash_attention_bwd_pallas

    b, hq, hkv, s, d, _, block, _ = FLASH_CALLS[call]
    q = _sds((b, hq, s, d), jnp.bfloat16, one_chip)
    kv = _sds((b, hkv, s, d), jnp.bfloat16, one_chip)
    vec = _sds((b, hq, s), jnp.float32, one_chip)
    fn = jax.jit(lambda q, k, v, lse, delta, do: flash_attention_bwd_pallas(
        q, k, v, lse, delta, do, causal=True, scale=d ** -0.5,
        block_q=block, block_kv=block))
    compiled = fn.lower(q, kv, kv, vec, vec, q).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_decode_attention_compiles_at_1b_widths(one_chip):
    from ray_tpu.ops.pallas.decode_attention import decode_attention

    slots, seq = 8, 8192
    q = _sds((slots, 1, H, D), jnp.bfloat16, one_chip)
    kv = _sds((slots, seq, KV, D), jnp.bfloat16, one_chip)
    lens = _sds((slots,), jnp.int32, one_chip)
    fn = jax.jit(lambda q, k, v, n: decode_attention(q, k, v, n,
                                                     scale=D ** -0.5))
    compiled = fn.lower(q, kv, kv, lens).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (slots, query rows, kv heads, head dim, block, table columns, pool layers
# and blocks): the 1b engine's two block sizes, then the dense serve cells'
# own (mistral-7b-l16: 32 slots of a 4,096-token max_seq over a 49,152-token
# pool), the block-diffusion cell's (4 positions x 32 heads a slot) and the
# state-space cell's one attention layer in its period (2 KV heads)
PAGED_DECODE_SHAPES = {
    "1b-block64": (8, H, KV, D, 64, 128, CFG.n_layers, 1025),
    "1b-block16": (8, H, KV, D, 16, 512, CFG.n_layers, 4097),
    "dense-serve-cells": (32, 32, 8, 128, 64, 64, 16, 769),
    "blockdiff-cell": (128, 128, 4, 128, 64, 32, 7, 3073),
    "state-space-cell": (192, 32, 2, 128, 64, 32, 1, 6145),
}


@pytest.mark.parametrize("shape", PAGED_DECODE_SHAPES)
def test_paged_decode_attention_compiles_at_1b_widths(one_chip, shape):
    from ray_tpu.ops.pallas.paged_decode_attention import (
        paged_decode_attention)

    slots, h, kv, d, block_size, cols, layers, nb = PAGED_DECODE_SHAPES[shape]
    q = _sds((slots, 1, h, d), jnp.bfloat16, one_chip)
    pool = _sds((layers, nb, block_size, kv * d), jnp.bfloat16, one_chip)
    layer = _sds((), jnp.int32, one_chip)
    tables = _sds((slots, cols), jnp.int32, one_chip)
    lens = _sds((slots,), jnp.int32, one_chip)
    fn = jax.jit(lambda q, k, v, l, t, n: paged_decode_attention(
        q, k, v, l, t, n, scale=d ** -0.5))
    text = fn.lower(q, pool, pool, layer, tables, lens).compile().as_text()
    assert "tpu_custom_call" in text and "%paged_decode_attention" in text


@pytest.mark.parametrize("kind", ["full", "window"])
def test_paged_hybrid_decode_attention_compiles_at_the_moe_cells_widths(
        one_chip, kind):
    """`serve-moe-window-decode`'s own shapes: 64 heads, keys 192 and
    values 128 wide, 4 (full) or 8 (window) kv heads, block 64, 128
    slots, 40 blocks a table; the work list built once outside the call,
    as the decode step hands it over."""
    from ray_tpu.models import mimo_v2
    from ray_tpu.ops.pallas import paged_hybrid_decode_attention as pha

    cfg = mimo_v2.MimoV2Config(
        n_heads=64, n_kv_heads=4, swa_n_kv_heads=8, head_dim=192,
        v_head_dim=128, rotary_dim=64, window=128)
    slots, bs, cols, kv = 128, 64, 40, cfg.kv_heads(kind)
    layers, nb = (2, 5121) if kind == "full" else (5, 385)
    window = cfg.window if kind == "window" else None
    q = _sds((slots, 64, cfg.head_dim), jnp.bfloat16, one_chip)
    k_pool = _sds((layers, nb, bs, kv * cfg.head_dim), jnp.bfloat16,
                  one_chip)
    v_pool = _sds((layers, nb, bs, kv * cfg.v_head_dim), jnp.bfloat16,
                  one_chip)
    layer = _sds((), jnp.int32, one_chip)
    tables = _sds((slots, cols), jnp.int32, one_chip)
    lens = _sds((slots,), jnp.int32, one_chip)
    sink = _sds((64,), jnp.float32, one_chip)

    def attend(q, k, v, l, t, n, sink):
        return pha.paged_hybrid_decode_attention(
            mimo_v2.pack_queries(q, cfg, kind), k, v, l, t, n,
            work=pha.hybrid_work_list(n, bs, cols, window),
            scale=cfg.head_dim ** -0.5,
            k_slices=mimo_v2.key_slices(cfg, kind), dv=cfg.v_head_dim,
            window=window, sink=sink if window else None,
            name=f"paged_hybrid_decode_{kind}")

    compiled = jax.jit(attend).lower(
        q, k_pool, v_pool, layer, tables, lens, sink).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"paged_hybrid_decode_{kind}" in text


@pytest.mark.parametrize("kind, heads", [("full", 48), ("window", 64)])
def test_paged_hybrid_decode_attention_compiles_at_the_whole_cells_widths(
        one_chip, kind, heads):
    """`serve-moe-whole-mixed-decode`'s own shapes: 48 (full) or 64
    (window) query rows against 8 kv heads, keys and values 128 wide (a
    row of 1,024 lanes, one chunk a kv head, nothing packed), no sink,
    block 64, 128 slots, 72 blocks a table; a window of 512 is 9 blocks
    in ONE step (4.7 MB of double-buffered blocks in VMEM)."""
    from ray_tpu.models import laguna
    from ray_tpu.ops.pallas import paged_hybrid_decode_attention as pha

    cfg = laguna.LagunaConfig(heads=(48, 64, 64, 64, 48), n_kv_heads=8,
                              head_dim=128, window=512)
    slots, bs, cols = 128, 64, 72
    layers, nb = (2, 9217) if kind == "full" else (3, 1153)
    window = cfg.window_of(kind)
    assert pha.blocks_per_step(window, bs, cols) == (9 if window else 4)
    q = _sds((slots, heads, 128), jnp.bfloat16, one_chip)
    pool = _sds((layers, nb, bs, 8 * 128), jnp.bfloat16, one_chip)
    layer = _sds((), jnp.int32, one_chip)
    tables = _sds((slots, cols), jnp.int32, one_chip)
    lens = _sds((slots,), jnp.int32, one_chip)

    def attend(q, k, v, l, t, n):
        return pha.paged_hybrid_decode_attention(
            q, k, v, l, t, n, work=pha.hybrid_work_list(n, bs, cols, window),
            scale=cfg.scale(kind), k_slices=laguna.key_slices(cfg), dv=128,
            window=window, name=f"paged_hybrid_decode_{kind}")

    text = jax.jit(attend).lower(q, pool, pool, layer, tables,
                                 lens).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"paged_hybrid_decode_{kind}" in text


@pytest.mark.parametrize("blocks_per_step", [1, 4, 8])
def test_paged_mla_decode_compiles_at_the_latent_cells_widths(
        one_chip, blocks_per_step, monkeypatch):
    """`serve-mla-moe-decode`'s own shapes: 64 heads against latent rows
    of 512 + 64 padded to 640 lanes, block 64, 192 slots, 68 blocks a
    table; the work list built once outside the call, as the decode step
    hands it over."""
    from ray_tpu.ops.pallas import paged_mla_decode_attention as mla

    monkeypatch.setattr(mla, "BLOCKS_PER_STEP", blocks_per_step)
    slots, bs, cols, width = 192, 64, 68, mla.padded_row(512 + 64)
    assert width == 640
    q = _sds((slots, 64, width), jnp.bfloat16, one_chip)
    pool = _sds((5, 1 + slots * cols, bs, width), jnp.bfloat16, one_chip)
    layer = _sds((), jnp.int32, one_chip)
    tables = _sds((slots, cols), jnp.int32, one_chip)
    lens = _sds((slots,), jnp.int32, one_chip)

    def attend(q, pool, l, t, n):
        return mla.paged_mla_decode_kernel(
            q, pool, l, t, n, scale=0.13086, rank=512,
            work=mla.mla_work_list(n, bs, cols))

    text = jax.jit(attend).lower(q, pool, layer, tables,
                                 lens).compile().as_text()
    assert "tpu_custom_call" in text and "paged_mla_decode" in text


def test_ssm_decode_update_compiles_at_the_state_space_cells_widths(
        one_chip):
    """`serve-ssm-latent-moe-chat`'s own shapes: five layers' state of
    192 slots x 8 groups x (128 x 1024) float32 (3.75 GiB), aliased in
    and out: the program holds no second state and no layer's slice."""
    from ray_tpu.ops.pallas import ssm_decode_update as ssm

    L, S, G, N, W = 5, 192, 8, 128, 1024
    state = _sds((L, S, G, N, W), jnp.float32, one_chip)
    row = _sds((S, G, W), jnp.float32, one_chip)
    col = _sds((S, G, N), jnp.float32, one_chip)
    active = _sds((S,), jnp.bool_, one_chip)
    compiled = jax.jit(
        lambda st, xdt, dec, b, c, a: ssm.ssm_decode_update(
            st, 2, xdt, dec, b, c, a),
        donate_argnums=(0,)).lower(state, row, row, col, col,
                                   active).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_decode_update" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * L * S * G * N * W
    assert mem.temp_size_in_bytes < 4 * S * G * N * W / 8    # no layer copy


# ------------------------------------------------- the 1b serving programs
def _engine_shapes(one_chip, num_slots=8, cfg=CFG):
    """What LLMEngine(model="1b") builds by default, as shapes."""
    page = PagedConfig(num_blocks=1 + num_slots * cfg.max_seq // 64,
                       block_size=64, max_seq=cfg.max_seq)
    params = _on(one_chip, jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0))))
    cache = _on(one_chip, jax.eval_shape(
        lambda: init_paged_cache(cfg, page, num_slots)))
    return page, params, cache


def _weight_bytes(params) -> int:
    return sum(_nbytes(a) for a in jax.tree.leaves(params))


def _check_program(lowered, params, cache, want_kernel: bool):
    assert _largest_literal_hex(lowered.as_text()) < WEIGHT_LITERAL_HEX
    compiled = lowered.compile()
    if want_kernel:
        assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    weights = _weight_bytes(params)
    kv = sum(_nbytes(a) for a in jax.tree.leaves(cache))
    # the weights are arguments of the program, not part of it
    assert mem.argument_size_in_bytes >= weights + kv
    assert mem.generated_code_size_in_bytes < weights // 100
    assert _total_bytes(mem) < HBM_BYTES


def test_1b_paged_decode_step_compiles_with_weights_as_arguments(
        one_chip, as_tpu):
    params, cache, lowered = _lower_decode(one_chip)
    _check_program(lowered, params, cache, want_kernel=True)


def test_1b_paged_prefill_bucket_compiles_with_weights_as_arguments(
        one_chip, as_tpu):
    params, cache, lowered = _lower_prefill(one_chip)
    # the prompt's own attention is plain XLA (mha_reference): no kernel
    _check_program(lowered, params, cache, want_kernel=False)


def _lower_decode(one_chip, cfg=CFG, num_slots=8):
    page, params, cache = _engine_shapes(one_chip, num_slots, cfg)
    step = make_paged_decode_step(params, cfg, page)
    return params, cache, step.jitted.lower(
        params, cache,
        _sds((num_slots, page.max_blocks_per_seq), jnp.int32, one_chip),
        _sds((num_slots,), jnp.int32, one_chip),
        _sds((num_slots,), jnp.bool_, one_chip))


def _lower_prefill(one_chip, cfg=CFG, pad_len=512):
    page, params, cache = _engine_shapes(one_chip, cfg=cfg)
    prefill = make_paged_prefill(params, cfg, page)
    scalar = _sds((), jnp.int32, one_chip)
    return params, cache, prefill.jitted.lower(
        params, cache,
        _sds((page.max_blocks_per_seq,), jnp.int32, one_chip),
        _sds((1, pad_len), jnp.int32, one_chip), scalar, scalar,
        pad_len=pad_len)


def _lower_chunk(one_chip, cfg=CFG, pad_len=256):
    page, params, cache = _engine_shapes(one_chip, cfg=cfg)
    chunk = make_chunked_paged_prefill(params, cfg, page)
    scalar = _sds((), jnp.int32, one_chip)
    return params, cache, chunk.jitted.lower(
        params, cache,
        _sds((page.max_blocks_per_seq,), jnp.int32, one_chip),
        _sds((1, pad_len), jnp.int32, one_chip), scalar, scalar, scalar,
        pad_len=pad_len)


# The 1b engine shapes, and the same with the 128-wide heads of the
# benchmark's cells (16 x 128 = 32 x 64: same hidden size, same weights'
# bytes). The pool's rows are a token's KV heads side by side, ``KV x D``
# wide, so a head of 64 columns does not set the device's layout: a minor
# dimension under 128 would put the BLOCK axis on the lanes, and every
# program that touches rows or blocks would turn the whole pool row-major
# and back around its loop (ROADMAP S9).
CFG_D128 = dataclasses.replace(CFG, n_heads=16, head_dim=128)


@pytest.mark.parametrize("cfg", [CFG_D128, CFG], ids=["head128", "head64"])
@pytest.mark.parametrize("lower", [_lower_decode, _lower_prefill,
                                   _lower_chunk],
                         ids=["decode", "prefill512", "chunk256"])
def test_1b_serving_program_holds_no_second_pool(one_chip, as_tpu, lower,
                                                 cfg):
    """The pool is a donated argument that the layer scan carries and
    updates in place: the program's temporaries hold no copy of it, nor
    of one layer of it, and nothing in the program moves a whole layer."""
    _, cache, lowered = lower(one_chip, cfg)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert not _pool_movers(text, cache["k"])
    pool_bytes = _nbytes(cache["k"]) + _nbytes(cache["v"])
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pool_bytes // 2
    assert mem.alias_size_in_bytes >= pool_bytes     # donated, and reused


# ------------------------------ a weight is read once by the program made
# what the compiler itself puts between a weight and the product that
# reads it: a prefetch into fast memory, whole or in slices, put together
# again by a bitcast
_PREFETCH = {"copy-start", "copy-done", "slice-start", "slice-done",
             "bitcast", "custom-call:ConcatBitcast"}
_CALLED = re.compile(r"\w+=(%[\w.\-]+|\{[^}]*\})")


def _entry_users(text: str) -> dict:
    """{operand: [(instruction, opcode, its HLO line)]} of a compiled
    module's entry computation; the computations a line calls are not
    among its operands."""
    from benchmark.trace_reduce import parse_op

    users = {}
    for line in text[text.index("\nENTRY "):].splitlines()[2:]:
        name, opcode, _ = parse_op(line.strip().removeprefix("ROOT "))
        rhs = _CALLED.sub("", line.partition(" = ")[2])
        for operand in re.findall(r"%([\w.\-]+)", rhs):
            users.setdefault(operand, []).append((name, opcode, line))
    return users


def _readers(users: dict, name: str) -> list:
    """The instructions that read ``name``, the compiler's prefetches of
    it followed to what they feed."""
    out = []
    for user, opcode, line in users.get(name, []):
        out += (_readers(users, user) if opcode in _PREFETCH
                else [(user, opcode, line)])
    return out


def _columns_made(text: str, line: str) -> int:
    """Output columns of the products (``convolution``) inside the
    computation a fusion calls."""
    called = re.search(r"calls=(%[\w.\-]+)", line).group(1)
    body = text[text.index(f"\n{called} ("):]
    body = body[:body.index("\n}")]
    return sum(int(w) for w in re.findall(
        r"= \w+\[[\d,]*?(\d+)\]\S* convolution\(", body))


# the four cells with an expert layer, and their configurations
ROUTED_CELLS = {
    "serve-moe-window-decode": "mimo-v2.5-ep16-l7",
    "serve-mla-moe-decode": "axk1-ep16-l5",
    "serve-moe-whole-mixed-decode": "laguna-xs2-l5",
    "serve-ssm-latent-moe-chat": "nemotron3-super-ep4-l11",
}


def _routed_programs(cell, device):
    """(slots, decode step, bucket -> prefill) of a cell, lowered."""
    from benchmark import model_spec, sizing

    spec = model_spec.load_config(ROUTED_CELLS[cell])
    with open(os.path.join(model_spec.HERE, "cells", f"{cell}.json")) as f:
        deployment = json.load(f)["deployment"]
    return (deployment["num_slots"],
            *sizing.serve_programs(spec, deployment, device))


# the cells over a dense paged pool, ``tokens' KV heads side by side``:
# (configuration, {program: most GiB the compile may sum to}); "decode" is
# the cell's step for all slots (the block step where blocks are denoised)
GIB = 1024 ** 3
DENSE_POOL_CELLS = {
    "serve-batch-decode": ("mistral-7b-l16", {"decode": 10.1}),
    "serve-ssm-latent-moe-chat": ("nemotron3-super-ep4-l11",
                                  {"decode": 13.0}),
    # cells/serve-blockdiff-moe-decode.json: 12.50 the step, 11.92 and
    # 12.09 the 64 and 1,024 buckets (the code's 0.01 GiB beside them)
    "serve-blockdiff-moe-decode": ("sdar-30b-a3b-l7", {
        "decode": 12.55, "prefill64": 11.97, "prefill1024": 12.14}),
}


@pytest.mark.parametrize("cell, program", [
    (cell, program) for cell, (_, programs) in DENSE_POOL_CELLS.items()
    for program in programs])
def test_dense_pool_cells_program_holds_no_second_pool(topo, as_tpu, cell,
                                                       program):
    """The programs of the three cells' models that write and read a
    lane-dense pool, at the cells' own sizes: the pool is donated and
    updated in place, nothing copies or re-lays-out the pool or a layer
    of it (2 and 4 KV heads made the compiler do that around a write of
    whole ``(bs, KV, D)`` blocks), the temporaries hold nothing of its
    size, the step's attention is ``paged_decode_attention``, and the
    whole stays within what the cell's file states."""
    from benchmark import model_spec, sizing

    config, programs = DENSE_POOL_CELLS[cell]
    with open(os.path.join(model_spec.HERE, "cells", f"{cell}.json")) as f:
        deployment = json.load(f)["deployment"]
    decode, bucket = sizing.serve_programs(
        model_spec.load_config(config), deployment, topo.devices[0])
    lowered = (decode if program == "decode"
               else bucket(int(program.removeprefix("prefill"))))
    pool = lowered.args_info[0][1]["k"]
    pool = jax.ShapeDtypeStruct(pool.shape, pool.dtype)
    assert pool.shape[2:] == (deployment["kv_block_size"], pool.shape[3])
    compiled = lowered.compile()
    text = compiled.as_text()
    assert not _pool_movers(text, pool)
    # the step's attention is the kernel under its own name (once in a
    # layer scan's body, once a layer where the layers are unrolled)
    kernels = re.findall(r"%paged_decode_attention[.\d]* = ", text)
    assert len(kernels) in ((1, pool.shape[0]) if program == "decode"
                            else (0,))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < _nbytes(pool) // 2
    assert mem.alias_size_in_bytes >= 2 * _nbytes(pool)
    assert _total_bytes(mem) < programs[program] * GIB


@pytest.mark.parametrize("program", ["decode", "prefill64"])
def test_state_space_cells_program_reads_each_weight_once(
        topo, one_chip, as_tpu, program):
    """`serve-ssm-latent-moe-chat`'s decode step (192 slots) and its
    smallest prefill bucket at the published widths. The in-projection
    made as ONE product and split was computed again by the chip's
    compiler for each of its four consumers in a decode layer
    (``fusion.465``, ``.465.remat``, ``.remat2``, ``.remat3``: 18 reads
    of a 152 MB ``w_in`` a step where 5 are needed; PERF.md section 6,
    PR 40). Held here: every column of every ``w_in`` is made once, by
    fusions that take the stored matrix itself (no copy or slice of it
    beside them, none a rematerialisation), and each other large matrix
    of the step has one reader."""
    slots, decode, bucket = _routed_programs("serve-ssm-latent-moe-chat",
                                             topo.devices[0])
    assert slots == 192
    text = (decode if program == "decode" else bucket(64)).compile().as_text()
    users = _entry_users(text)
    weights = {}
    for name in users:
        m = re.match(r"params__(?:layers___\d+___)?([a-z_0-9]+?)__\.\d+$",
                     name)
        if m:
            weights.setdefault(m.group(1), []).append(name)
    assert len(weights["w_in"]) == 5
    for w_in in weights["w_in"]:
        direct = users[w_in]
        assert {opcode for _, opcode, _ in direct} == {"fusion"}, direct
        assert not [n for n, _, _ in direct if "remat" in n], direct
        assert sum(_columns_made(text, line)
                   for _, _, line in direct) == 18560, direct
    for kind, count in [("w_out", 5), ("w_fc1", 5), ("w_fc2", 5),
                        ("ws_up", 5), ("ws_down", 5), ("lm_head", 1)]:
        assert len(weights[kind]) == count
        for weight in weights[kind]:
            readers = {n for n, _, _ in _readers(users, weight)}
            assert len(readers) == 1, (weight, readers)
            assert not [n for n in readers if "remat" in n], readers


# the temporaries of `serve-mla-moe-decode`'s 2,048 bucket: 0.654 GiB by
# this compile since the combine is a kernel (PR 50: no (2,048, 8, 7,168)
# float32 gather beside the grouped products' rows; 1.036 before), held
# 5% over
LATENT_BUCKET_TEMP_GIB = 0.69


def test_latent_cells_largest_bucket_attends_in_the_flash_kernel(
        topo, as_tpu):
    """`serve-mla-moe-decode`'s 2,048 bucket, where every prompt of the
    cell lands: each of the five layers' expanded attention is ONE
    ``flash_attention_fwd`` call, and no float32 array of a layer's
    whole scores (64 x 2,048 x 2,048 x 4 B = 1.07 GB in XLA) is left in
    the program. What peaks in the bucket's temporaries is then the
    expert layer's float32 rows (the grouped product's (17,920 x 7,168)
    output; the combine gathers nothing): held as the ceiling."""
    _, _, bucket = _routed_programs("serve-mla-moe-decode", topo.devices[0])
    compiled = bucket(2048).compile()
    text = compiled.as_text()
    kernels = re.findall(r"= \([^=]*\) custom-call\([^\n]*"
                         r"flash_attention_fwd/pallas_call", text)
    assert len(kernels) == 5
    assert all(k.startswith("= (bf16[64,2048,128]") for k in kernels)
    # a layer's scores whole: any float32 (..., 2048, 2048) of several heads
    assert not re.findall(r"= f32\[(?:\d+,)+2048,2048\]", text)
    assert (compiled.memory_analysis().temp_size_in_bytes
            < LATENT_BUCKET_TEMP_GIB * 1024 ** 3)


# ------------------------------------ the grouped products' row tile (PR 45)
# a grouped product in a lowered module: tile groups, n_active, the rows,
# the held experts' stacked matrix
_GROUPED = re.compile(
    r"stablehlo\.custom_call @tpu_custom_call\([^\n]*grouped_expert_matmul"
    r"[^\n]*: \(tensor<(\d+)xi32>, tensor<1xi32>, tensor<(\d+)x(\d+)xbf16>, "
    r"tensor<(\d+)x\d+x\d+xbf16>\)")


@pytest.mark.parametrize("cell", ROUTED_CELLS)
def test_routed_cells_decode_step_keeps_the_16_row_tile(topo, as_tpu, cell):
    """An expert gets 4-8 rows of a decode step: every grouped product
    of the four routed cells' ``jit_step`` walks 16-row tiles (``M / 16``
    tile groups) over the worst case's rows, as before the tile was a
    rule."""
    slots, decode, _ = _routed_programs(cell, topo.devices[0])
    products = _GROUPED.findall(decode.as_text())
    assert products
    for tiles, rows, _, held in products:
        tiles, rows, held = int(tiles), int(rows), int(held)
        assert rows == tiles * 16
        # T * top_k + G * 15 rounded up to tiles, for some top_k of 8 / 22
        assert any(tiles == -(-(slots * k + held * 15) // 16)
                   for k in (8, 22)), (tiles, held)


def test_latent_cells_largest_bucket_takes_the_rules_tile(topo, as_tpu):
    """`serve-mla-moe-decode`'s 2,048 bucket (85 rows an expert): its
    twelve grouped products walk the tile that ``moe.row_tile`` gives at
    (2,048, 8, 192, 12), the row buffer is ``moe.pass_rows``' at that
    tile (PR 58: 3,584 rows of the worst case's 17,920, 252 MiB of dead
    rows, ``moe.bound_serves``; the products stand in the body of the
    loop over the buffer's passes, once a layer), and the bucket's
    temporaries stay under the ceiling."""
    from ray_tpu.models import moe

    tm = moe.row_tile(2048, 8, 192, 12)
    assert tm > 16
    _, _, bucket = _routed_programs("serve-mla-moe-decode", topo.devices[0])
    lowered = bucket(2048)
    products = _GROUPED.findall(lowered.as_text())
    assert len(products) == 12
    rows = moe.pass_rows(2048, 8, 192, 12, tm)
    assert rows == 3584 and moe.bound_serves(
        -(-(2048 * 8 + 12 * (tm - 1)) // tm) * tm - rows, (7168 + 2048) * 2)
    assert {(int(t), int(r)) for t, r, _, _ in products} == {
        (rows // tm, rows)}
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes < LATENT_BUCKET_TEMP_GIB * 1024 ** 3


@pytest.mark.parametrize("K, N", [(7168, 256), (2048, 1024)])
def test_grouped_matmul_compiles_at_128_rows(one_chip, K, N):
    """The largest tile against the latent model's two blocks: (128 x
    7,168) x (7,168 x 256) for gate and up, (128 x 2,048) x (2,048 x
    1,024) for down, both operands double-buffered beside a float32
    output inside the 16 MiB of scoped VMEM; ``_tn`` chooses those
    columns from the stored widths."""
    from ray_tpu.ops.pallas import grouped_matmul as gm

    width = {256: 2048, 1024: 7168}[N]            # the stored matrix's
    assert gm._tn(K, width, 2) == N
    tm, tiles = 128, 140
    fn = jax.jit(lambda l, w, g, n: gm.grouped_matmul(
        l, w, g, n, tm=tm, out_dtype=jnp.float32,
        name="grouped_expert_matmul_prefill"))
    text = fn.lower(_sds((tiles * tm, K), jnp.bfloat16, one_chip),
                    _sds((12, K, width), jnp.bfloat16, one_chip),
                    _sds((tiles,), jnp.int32, one_chip),
                    _sds((), jnp.int32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "grouped_expert_matmul_prefill" in text


@pytest.mark.parametrize("K, N", [(2048, 1536), (1536, 2048)])
def test_the_weight_gradients_kernel_compiles_at_a_train_steps_tiles(
        one_chip, K, N):
    """``grouped_expert_matmul_dw`` at the train cell's widths (an
    expert's gate / up: rows 2,048 wide against cotangents 1,536 wide;
    its down: the reverse), 128-row tiles in bfloat16 contracted over
    the ROWS, a float32 (K, tn) accumulator in VMEM beside both operands
    double-buffered; and the combine's kernel for one run of 16,384
    tokens, whose pairs ride in SMEM (32,768 tokens in one call were
    refused: 1.1 MiB of a core's 1 MiB)."""
    from ray_tpu.ops.pallas import expert_combine, grouped_matmul as gm

    tm, tiles = 128, 96
    fn = jax.jit(lambda l, d, g, n: gm.grouped_matmul_dw(
        l, d, g, n, tm=tm, groups=16))
    text = fn.lower(_sds((tiles * tm, K), jnp.bfloat16, one_chip),
                    _sds((tiles * tm, N), jnp.bfloat16, one_chip),
                    _sds((tiles,), jnp.int32, one_chip),
                    _sds((), jnp.int32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text and gm.NAME_DW in text
    T, k = 32768, 4
    assert expert_combine._CALL_PAIRS // k == T // 2
    run = jax.jit(expert_combine.expert_combine).lower(
        _sds((T * k + 2048, 2048), jnp.float32, one_chip),
        _sds((T, k), jnp.int32, one_chip), _sds((T, k), jnp.bool_, one_chip),
        _sds((T, k), jnp.float32, one_chip)).compile().as_text()
    assert run.count("tpu_custom_call") == 2


# --------------------------- the combine follows the pairs held (PR 50)
# (tokens, top_k, the rows' width, rows of the buffer): the three
# share-held cells' longest bucket and decode step
COMBINE_CALLS = {
    "axk1-2048": (2048, 8, 7168, 17920),
    "mimo_v2-1024": (1024, 8, 4096, 9216),
    "nemotron_h-1024": (1024, 22, 1024, 30592),
    "axk1-decode-192": (192, 8, 7168, 1728),
    "mimo_v2-decode-128": (128, 8, 4096, 1264),
    "nemotron_h-decode-192": (192, 22, 1024, 6144),
}


@pytest.mark.parametrize("call", COMBINE_CALLS)
def test_expert_combine_compiles_at_the_share_held_cells_shapes(one_chip,
                                                                call):
    """The combine's kernel at the three share-held models' widths and
    rows: the chip's compiler takes the copies of aligned 8-row groups
    (it refuses a one-row slice of the tiled float32 buffer), the ring
    and the output block fit the scoped VMEM, the pair lists fit SMEM,
    and nothing outside the kernel is a temporary."""
    from ray_tpu.ops.pallas import expert_combine as ec

    T, k, h, M = COMBINE_CALLS[call]
    compiled = jax.jit(ec.expert_combine).lower(
        _sds((M, h), jnp.float32, one_chip), _sds((T, k), jnp.int32, one_chip),
        _sds((T, k), jnp.bool_, one_chip),
        _sds((T, k), jnp.float32, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "expert_combine" in text
    assert compiled.memory_analysis().temp_size_in_bytes < T * k * 16


def _combine_kernels(text: str) -> int:
    return len(re.findall(r'kernel_name = "expert_combine"', text))


@pytest.mark.parametrize("cell, bucket, top_k, width, layers, decode_too", [
    ("serve-mla-moe-decode", 2048, 8, 7168, 4, True),
    ("serve-moe-window-decode", 1024, 8, 4096, 6, True),
    ("serve-ssm-latent-moe-chat", 1024, 22, 1024, 5, False)])
def test_share_held_cells_combine_in_the_kernel(topo, as_tpu, cell, bucket,
                                                top_k, width, layers,
                                                decode_too):
    """Where a chip holds a share of the experts, the longest bucket
    combines in the kernel, once a routed layer (in the body of the loop
    over its bounded buffer's passes, PR 58), and holds no (T, top_k,
    h) float32 array: nothing gathers every pair's row. So does the
    decode step where a sixteenth is held; where a quarter is
    (`nemotron_h`, a gather of 17 MB) the step keeps the XLA form, which
    is faster there (``expert_combine.kernel_serves``), and is the
    parent's program."""
    slots, decode, prefill = _routed_programs(cell, topo.devices[0])
    for rows, lowered, kernel in ((slots, decode, decode_too),
                                  (bucket, prefill(bucket), True)):
        text = lowered.as_text()
        assert _combine_kernels(text) == (layers if kernel else 0)
        gather = f"tensor<{rows}x{top_k}x{width}xf32>"
        assert (gather in text) != kernel


def test_whole_held_cells_combine_in_xla(topo, as_tpu):
    """`laguna` and `sdar` hold their whole expert sets: every pair is
    placed, the dense gather moves no row in vain, and their decode step
    and block step keep the XLA form (the (T, top_k, h) float32 gather
    is there, the kernel is not)."""
    from benchmark import model_spec, sizing

    slots, decode, _ = _routed_programs("serve-moe-whole-mixed-decode",
                                        topo.devices[0])
    text = decode.as_text()
    assert _combine_kernels(text) == 0
    assert f"tensor<{slots}x8x2048xf32>" in text
    cell = "serve-blockdiff-moe-decode"
    with open(os.path.join(model_spec.HERE, "cells", f"{cell}.json")) as f:
        deployment = json.load(f)["deployment"]
    block_step, _ = sizing.serve_programs(
        model_spec.load_config(DENSE_POOL_CELLS[cell][0]), deployment,
        topo.devices[0])
    text = block_step.as_text()
    assert _combine_kernels(text) == 0
    assert f"tensor<{deployment['num_slots'] * 4}x8x2048xf32>" in text


# ------------------------------------------- the engine's pick of a token
@pytest.mark.parametrize("slots, vocab", [(32, 32768), (192, 20480)])
def test_sample_ids_compiles_at_the_cells_shapes(one_chip, slots, vocab):
    """The chat cell's turn and the widest cell's: the draw stays on the
    device (no callback to the host) and fuses (no second copy of the
    logits: what the Gumbel form is kept for)."""
    from ray_tpu.serve.llm import sample_ids

    key = jax.eval_shape(lambda: jax.random.key(0))
    logits = _sds((slots, vocab), jnp.float32, one_chip)
    ints = _sds((slots,), jnp.int32, one_chip)
    lowered = jax.jit(sample_ids).lower(
        logits, _sds((slots,), jnp.float32, one_chip),
        _sds(key.shape, key.dtype, one_chip), ints, ints)
    assert "module @jit_sample_ids" in lowered.as_text()
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "callback" not in text and "custom-call" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3 * _nbytes(logits)
    assert mem.output_size_in_bytes <= 4096     # (slots,) int32, tiled


# ------------------------- the dense projections are read where they lie
def _placed(tree):
    """Shapes as ``llama.serving_layout`` leaves arrays: ``wq`` / ``wk`` /
    ``wv`` in the program's own device layout, on their own sharding."""
    from jax.experimental.layout import Format, Layout

    if "layers" not in tree:
        return tree
    layers = dict(tree["layers"])
    for name, order in llama.SERVING_LAYOUT.items():
        a = layers[name]
        layers[name] = _sds(a.shape, a.dtype, Format(
            Layout(major_to_minor=order), a.sharding))
    return {**tree, "layers": layers}


@pytest.mark.parametrize("cell, config, program", [
    ("serve-batch-decode", "mistral-7b-l16", "decode"),
    ("serve-batch-decode", "mistral-7b-l16", "prefill256"),
    ("serve-looped-dense-decode", "ouro-2.6b", "decode"),
    ("serve-looped-dense-decode", "ouro-2.6b", "prefill128")])
@pytest.mark.parametrize("lies", ["as_made", "placed"])
def test_placed_projections_are_not_staged_by_the_layer_scan(
        topo, one_chip, as_tpu, monkeypatch, lies, cell, config, program):
    """The two dense models at their cells' sizes. With the weights as
    they are made, the layer scan of the decode step and of a prefill
    bucket stages a layer's slice of ``wq``, ``wk`` and ``wv`` (a
    ``dynamic-slice`` fusion of ``bf16[1,E,H,128]`` each) and copies it
    into the order the product reads. Lowered for the layout ``place``
    gives the engine's weights, the bucket holds neither and the step at
    most ``wv``'s. (``benchmark.sizing.on`` lowers with default layouts;
    the formats are steered in from here.)"""
    from benchmark import model_spec, sizing

    if lies == "placed":
        plain = sizing.on
        monkeypatch.setattr(sizing, "on",
                            lambda sharding, tree: _placed(plain(sharding,
                                                                 tree)))
    spec = model_spec.load_config(config)
    with open(os.path.join(model_spec.HERE, "cells", f"{cell}.json")) as f:
        deployment = json.load(f)["deployment"]
    decode, bucket = sizing.serve_programs(spec, deployment, topo.devices[0])
    compiled = (decode if program == "decode" else
                bucket(int(program.removeprefix("prefill")))).compile()
    orders = {name: fmt.layout.major_to_minor for name, fmt in
              compiled.input_formats[0][0]["layers"].items()}
    for name, order in llama.SERVING_LAYOUT.items():
        assert orders[name] == (order if lies == "placed" else (0, 1, 2, 3))
    assert orders["wo"] == (0, 1, 2, 3) and orders["w_gate"] == (0, 1, 2)
    text = compiled.as_text()
    slice_ = rf"bf16\[1,{spec['hidden_size']},\d+,128\]"
    staged = re.findall(rf"dynamic-slice_fusion[.\d]* = {slice_}", text)
    copied = re.findall(rf" = {slice_}\S* copy\(", text)
    if lies == "as_made":
        assert len(staged) == 3 and copied
    else:
        most = 1 if program == "decode" else 0
        assert len(staged) <= most and len(copied) <= most


# ----------------------------------------------------------------- CPU only
def test_tiny_decode_module_holds_no_weights():
    """A closed-over array lowers to a literal. `tiny` has 89 MB of
    weights; with them as arguments the decode module is a few 10 KB."""
    cfg = llama.CONFIGS["tiny"]
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0)))
    page = PagedConfig(num_blocks=33, block_size=64, max_seq=cfg.max_seq)
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, page, 4))
    step = make_paged_decode_step(params, cfg, page)
    text = step.jitted.lower(
        params, cache,
        jax.ShapeDtypeStruct((4, page.max_blocks_per_seq), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.bool_)).as_text()
    assert len(text) < 1_000_000
    assert _largest_literal_hex(text) < WEIGHT_LITERAL_HEX
