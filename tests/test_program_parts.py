"""Every device operation of the decode step, a prefill and the train step
lies under a named part of the block (``ray_tpu.util.profiling.PARTS``,
and ``SSM_PARTS`` for what a state-space model adds).

The programs of the five serving models and the train step are lowered
here on the CPU at tiny sizes, and the ``op_name`` that every instruction
carries (``metadata={op_name="jit(step)/.../mlp/dot_general"}``; on a chip
the same path is the ``tf_op`` stat of the operation's event in a capture)
is read as the benchmark's reader reads it: the innermost component that
is a name of ``PARTS`` is the part, a transform's wrapper
(``transpose(jvp(mlp))``) peeled off. What is read is the module the
compiler is GIVEN: what the CPU's compiler makes of it (a fusion of its
own around a widened operand, an expanded cumulative sum) carries no path
and says nothing of the chip's, whose unnamed share the benchmark measures
(``decode_unnamed_dev_ms``). The kernels do not run on the CPU: their XLA
oracles stand under ``attention`` and the kernels' own names."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import axk1, laguna, llama, mimo_v2, nemotron_h, sdar
from ray_tpu.models.serving import serving_model
from ray_tpu.models.training import (OptimizerConfig, init_train_state,
                                     make_train_step)
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.parallel.sharding import ShardingRules
from ray_tpu.util import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS, MAX_SEQ, BLOCK, PAD = 3, 64, 8, 32
HEAVY = ("dot", "convolution", "sort", "gather", "scatter", "reduce",
         "reduce-window", "dynamic-update-slice", "custom-call")
MODELS = {
    "dense": lambda: llama.CONFIGS["debug"],
    "mimo_v2": mimo_v2.MimoV2Config,
    "axk1": axk1.AxK1Config,
    "laguna": laguna.LagunaConfig,
    "nemotron_h": nemotron_h.NemotronHConfig,
}
ROUTED = ("mimo_v2", "axk1", "laguna", "nemotron_h")
VOCABULARY = (profiling.PARTS + profiling.SSM_PARTS
              + profiling.BLOCK_PARTS + profiling.LOOP_PARTS
              + profiling.CONV_PARTS + profiling.EXPERT_GRAD_PARTS)
# the parts under which a decode step writes its per-request state: the
# KV pools everywhere; a state-space model's recurrent state and
# convolution columns under its own
STATE_WRITES = dict.fromkeys(MODELS, {"kv_store"})
STATE_WRITES["nemotron_h"] = {"kv_store", "ssm_update", "ssm_conv"}

_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][a-z\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)|"
                     r"branch_computations=\{([^}]*)\}")
_PEEL = re.compile(r"^(?:\w+\()*([^()]*)\)*$")


def part_of(op_name):
    """The innermost component of an ``op_name`` path that is a part."""
    for comp in reversed(op_name.split("/")):
        inner = _PEEL.match(comp)
        if inner and inner.group(1) in VOCABULARY:
            return inner.group(1)
    return None


def hlo_text(lowered):
    """The module as the compiler is given it, with every instruction's
    ``metadata``."""
    from jax._src.lib import _jax

    options = _jax.HloPrintOptions()
    options.print_metadata = True
    return lowered.compiler_ir(dialect="hlo").get_hlo_module().to_string(
        options)


def operations(text):
    """[(opcode, result type, op_name)] of the entry computation and of
    the computations its loops, calls and conditionals run (a reducer is
    its reduction's own business). An instruction of a called function
    names itself from the call on (``mul``); the call's own path is put
    before it, as the compiler does when it inlines the call."""
    comps, name, entry = {}, None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(2)
            comps[name] = []
            if head.group(1):
                entry = name
        elif name is not None and line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    root = next(m.group(1).split("/")[0] for m in map(
        _OP_NAME.search, comps[entry]) if m and m.group(1).startswith("jit("))
    out, todo, seen = [], [(entry, "")], set()
    while todo:
        comp, prefix = todo.pop()
        if (comp, prefix) in seen:
            continue
        seen.add((comp, prefix))
        for line in comps[comp]:
            m = _INSTR.match(line)
            if not m:
                continue
            opcode = m.group(3)
            path = _OP_NAME.search(line)
            path = path.group(1) if path else ""
            if path and path.split("/")[0] != root:
                path = prefix + "/" + path
            if opcode in ("while", "call", "conditional"):
                for one, many in _CALLED.findall(line):
                    todo += [(c.strip().lstrip("%"), path)
                             for c in ([one] if one else many.split(","))]
                continue
            if 'custom_call_target="Sharding"' not in line:  # no operation
                out.append((opcode, m.group(2), path))
    return out


@pytest.fixture(scope="module")
def programs():
    """model -> {"decode": compiled text, "prefill": ..., "cfg": ...},
    compiled once for all the cases."""
    out = {}

    def get(model):
        if model not in out:
            cfg = MODELS[model]()
            served = serving_model(cfg)
            params = served.init_params(jax.random.key(0))
            p = served.paged(params, num_slots=SLOTS, max_seq=MAX_SEQ,
                             block_size=BLOCK, pool_tokens=SLOTS * MAX_SEQ)
            decode = p.decode.jitted.lower(
                params, p.cache, p.alloc.device_tables(),
                jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,), bool))
            rows = jax.tree.map(jnp.asarray, p.alloc.table_rows(0))
            prefill = p.prefill.jitted.lower(
                params, p.cache, rows, jnp.zeros((1, PAD), jnp.int32),
                jnp.int32(PAD - 3), jnp.int32(0), pad_len=PAD)
            out[model] = {"cfg": cfg, "cache": p.cache,
                          "decode": hlo_text(decode),
                          "prefill": hlo_text(prefill)}
        return out[model]

    return get


@pytest.fixture(scope="module")
def train_text():
    cfg = llama.LlamaConfig(vocab_size=256, hidden=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, head_dim=16, mlp_dim=128,
                            max_seq=64, dtype=jnp.float32, remat=True)
    rules = ShardingRules()
    opt = OptimizerConfig(warmup_steps=1).make()
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    with jax.sharding.set_mesh(mesh):
        state, _ = init_train_state(
            lambda key: llama.init_params(cfg, key),
            llama.param_logical_axes(cfg), opt, mesh, rules,
            jax.random.key(0))
        step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg, rules),
                               opt, mesh, rules, donate=False)
        return hlo_text(step.lower(
            state, {"tokens": jnp.zeros((2, 64), jnp.int32)}))


def _named_share(text):
    """(heavy operations, those of them under no part). A layer scan's
    own stacking of what its body returns (``.../while/body/
    dynamic_update_slice``: a train step's saved activations and stacked
    gradients) is ``lax.scan``'s, written under the scan's path and not
    the block's: left out here, and ``unnamed`` on the chip."""
    heavy = [(op, path) for op, _, path in operations(text) if op in HEAVY
             and not path.endswith("/while/body/dynamic_update_slice")]
    bare = [(op, path) for op, path in heavy if part_of(path) is None]
    return len(heavy), bare


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_serving_programs_operations_lie_under_a_part(programs, model,
                                                          program):
    n, bare = _named_share(programs(model)[program])
    assert n > 10
    assert len(bare) <= 0.05 * n, (n, bare)


def test_the_train_steps_operations_lie_under_a_part(train_text):
    n, bare = _named_share(train_text)
    assert n > 30
    assert len(bare) <= 0.05 * n, (n, bare)


@pytest.mark.parametrize("model", ROUTED)
def test_sort_under_dispatch_and_topk_under_router(programs, model):
    ops = operations(programs(model)["decode"])
    sorts = [path for op, _, path in ops if op == "sort"
             and "top_k" not in path]
    assert sorts and {part_of(p) for p in sorts} == {"expert_dispatch"}
    topk = [path for _, _, path in ops if "top_k" in path]
    assert topk and {part_of(p) for p in topk} == {"router"}, topk
    assert any(part_of(p) == "expert_combine" for _, _, p in ops)


@pytest.mark.parametrize("model", sorted(MODELS))
def test_vocabulary_dot_under_head_and_pool_writes_under_kv_store(programs,
                                                                 model):
    got = programs(model)
    ops = operations(got["decode"])
    vocab = got["cfg"].vocab_size
    wide = [path for op, result, path in ops
            if op == "dot" and re.search(rf"\[(\d+,)*{vocab}\]", result)]
    assert wide and {part_of(p) for p in wide} == {"head"}, wide
    pools = {tuple(a.shape) for a in jax.tree.leaves(got["cache"])
             if a.ndim >= 4}
    writes = [path for op, result, path in ops
              if op in ("scatter", "dynamic-update-slice") and any(
                  "[" + ",".join(map(str, s)) + "]" in result for s in pools)]
    assert writes and {part_of(p) for p in writes} == STATE_WRITES[model], \
        writes


# ----------------------------------- the kernels, in a module made for a TPU
def _step_for_a_tpu(cfg, monkeypatch):
    """The model's step for all slots (the block step where blocks are
    denoised), traced with the dispatchers told they are on a TPU and
    lowered for one: the kernels are custom calls in it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    served = serving_model(cfg)
    params = served.init_params(jax.random.key(0))
    p = served.paged(params, num_slots=SLOTS, max_seq=MAX_SEQ,
                     block_size=BLOCK, pool_tokens=SLOTS * MAX_SEQ)
    running = (jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,), bool))
    if p.decode is None:
        step, _ = served.block_denoise(params, p)
        shape = (SLOTS, cfg.block_length)
        running = (jnp.zeros(shape, jnp.int32), jnp.zeros(shape, bool),
                   running[1])
    else:
        step = p.decode
    return step.jitted.trace(params, p.cache, p.alloc.device_tables(),
                             *running).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("model, calls", [
    ("dense", 1), ("nemotron_h", 1), ("sdar", 4)])
def test_a_dense_pools_step_attends_in_the_kernel_of_its_name(
        model, calls, monkeypatch):
    """The step over a dense paged pool (the dense decoder's layer scan,
    the state-space model's one attention layer in its period, the block
    step's four layers): its attention is the custom call whose
    ``kernel_name`` is ``paged_decode_attention``, under the part of
    that name, and no other kernel attends. The benchmark finds the
    kernel's time and its roofline share by those two names."""
    cfg = {**MODELS, "sdar": sdar.SdarConfig}[model]()
    lowered = _step_for_a_tpu(cfg, monkeypatch)
    kernels = re.findall(r'kernel_name = "(\w+)"', lowered.as_text())
    assert kernels.count("paged_decode_attention") == calls
    assert not [k for k in kernels if "attention" in k
                and k != "paged_decode_attention"]
    paths = [_OP_NAME.search(line).group(1)
             for line in hlo_text(lowered).splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(paths) == len(kernels)
    mine = [p for p in paths if part_of(p) == "paged_decode_attention"]
    assert len(mine) == calls, paths


def test_a_share_held_steps_combine_is_the_kernel_under_its_part(
        monkeypatch):
    """Where a share of the experts is held (2 of 16 here) the step made
    for a TPU combines in the custom call ``expert_combine``, once a
    routed layer; its path lies under the part of that name (which
    ``decode_expert_dispatch_dev_ms`` and the ``prefill_expert*_dev_ms``
    metrics read) and every scope on the path is a name of the
    vocabulary. The default config holds the set whole: no such call."""
    import dataclasses

    whole = mimo_v2.MimoV2Config()
    assert 'kernel_name = "expert_combine"' not in _step_for_a_tpu(
        whole, monkeypatch).as_text()
    cfg = dataclasses.replace(whole, experts_held=(4, 2))
    lowered = _step_for_a_tpu(cfg, monkeypatch)
    kernels = re.findall(r'kernel_name = "(\w+)"', lowered.as_text())
    assert kernels.count("expert_combine") == sum(cfg.moe_layers) > 0
    paths = [_OP_NAME.search(line).group(1)
             for line in hlo_text(lowered).splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    mine = [p for p in paths if part_of(p) == "expert_combine"]
    assert len(mine) == sum(cfg.moe_layers), paths
    for path in mine:
        root, *scopes, primitive = path.split("/")
        assert root.startswith("jit(") and primitive == "pallas_call"
        assert scopes and set(scopes) <= set(VOCABULARY), path


def _without_locations(lowered):
    """The module's text and its kernels' bodies with no file, line or
    call stack in them: a kernel's body travels serialised inside its
    call's ``backend_config``, locations and all, so it is parsed and
    printed again."""
    import base64
    import json

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    text = lowered.compiler_ir().operation.get_asm(enable_debug_info=False)
    config = re.compile(r'backend_config = "([^"]*)"')
    bodies = []
    for raw in config.findall(text):
        body = json.loads(raw.replace("\\22", '"').replace(
            "\\5C", "\\")).get("custom_call_config", {}).get("body")
        if body:
            ctx = mlir.make_ir_context()
            ctx.allow_unregistered_dialects = True
            with ctx:
                bodies.append(ir.Module.parse(base64.b64decode(
                    body)).operation.get_asm(enable_debug_info=False))
    return config.sub('backend_config = "..."', text), bodies


# sha256 of the step made for a TPU at the model's default (tiny) config,
# and of its hybrid decode kernels' bodies. Recorded anew by PR 58, which
# meant to move them by ONE thing: ``moe.COUNTERS`` has a sixth name
# (``expert_extra_passes``, the constant 0 in a step whose buffer holds
# the worst case, as every decode step's does). With that element taken
# out again (the name, and the constant in ``experts_by_share``'s stack)
# the PR's tree gave the digests recorded on the tree BEFORE the dense
# pools' kernel became a caller of the hybrid body (PR 49's parent):
# 72aa5a7a...306a394 and 7062fc35...3901839.
HYBRID_STEPS = {
    "mimo_v2": ("b8f005c3b5201ad0049d21c967740b8f7de7a16794a998e7bb632a25354f"
                "5e7f", 2),
    "laguna": ("1a8626108ef42a8a3c7d51186790dad55fc07257d1f4a56962557a31feaf"
               "649f", 2),
}


@pytest.mark.parametrize("model", HYBRID_STEPS)
def test_the_hybrid_models_step_is_the_one_it_was(model, monkeypatch):
    """The hybrid kernel's body now serves the dense pools too. What it
    is for its first callers did not move with that: their decode step,
    the kernels' bodies and blocks a step included, is text for text the
    one recorded. (A change that means to move these programs records
    the new digest here, and says so.)"""
    import hashlib

    text, bodies = _without_locations(
        _step_for_a_tpu(MODELS[model](), monkeypatch))
    digest, kinds = HYBRID_STEPS[model]
    hybrid = {b for b in bodies if "paged_hybrid_decode" in b}
    assert len(hybrid) == kinds            # a body a kind of layer
    whole = hashlib.sha256("\n".join([text, *bodies]).encode())
    assert whole.hexdigest() == digest


def test_what_a_state_space_model_adds_is_under_its_own_parts(programs):
    """Each of the six names is met where it belongs: the scan in the
    prefill alone, the state update in the decode step alone, the
    projections, the convolution, the gate and norm and the latent
    projections in both; the expert layer keeps the names it has."""
    got = programs("nemotron_h")
    seen = {prog: {part_of(path) for _, _, path in operations(got[prog])}
            for prog in ("decode", "prefill")}
    both = {"ssm_proj", "ssm_conv", "ssm_gate_norm", "latent_proj"}
    assert both | {"ssm_update"} <= seen["decode"]
    assert both | {"ssm_scan"} <= seen["prefill"]
    assert "ssm_scan" not in seen["decode"]
    assert "ssm_update" not in seen["prefill"]
    shared = {"embed", "attn_proj", "kv_store", "attention", "mlp", "router",
              "expert_dispatch", "expert_combine", "expert_layer",
              "shared_expert", "grouped_expert_matmul", "head"}
    assert shared <= seen["decode"]
    assert shared - {"grouped_expert_matmul"} <= seen["prefill"]
    assert "grouped_expert_matmul_prefill" in seen["prefill"]


def test_the_train_step_splits_into_forward_recompute_and_backward(
        train_text):
    paths = [path for op, _, path in operations(train_text) if op in HEAVY]
    recompute = {part_of(p) for p in paths if "rematted_computation" in p}
    backward = {part_of(p) for p in paths if "transpose(jvp" in p
                and "rematted_computation" not in p}
    forward = {part_of(p) for p in paths if "transpose(jvp" not in p
               and "rematted_computation" not in p}
    block = {"attn_proj", "attention", "mlp"}
    assert block <= recompute and block <= backward and block <= forward
    assert {"embed", "head", "loss", "optimizer"} <= forward
    assert "optimizer" not in backward | recompute


@pytest.fixture(scope="module")
def lfm2_train_text():
    """The train step of the gated-convolution model held by share, made
    for a TPU (the kernels' calls are in it; nothing runs)."""
    from ray_tpu.models import lfm2

    cfg = lfm2.Lfm2Config(experts_held=(2, 2), dtype=jnp.float32)
    rules = ShardingRules()
    opt = lfm2.frozen_buffers(OptimizerConfig(warmup_steps=1).make(),
                              lfm2.param_shapes(cfg))
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    with jax.sharding.set_mesh(mesh), pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        state, _ = init_train_state(
            lambda key: lfm2.init_params(cfg, key),
            lfm2.param_logical_axes(cfg), opt, mesh, rules,
            jax.random.key(0))
        step = make_train_step(lambda p, b: lfm2.loss_fn(p, b, cfg, rules),
                               opt, mesh, rules, donate=False)
        return hlo_text(step.trace(
            state, {"tokens": jnp.zeros((2, 128), jnp.int32)}).lower(
                lowering_platforms=("tpu",)))


def test_the_convolution_models_train_step_lies_under_its_parts(
        lfm2_train_text):
    """Forward, recomputation AND backward: ``jax.named_scope`` survives
    the transposition, so a backward operation carries its forward's
    part; the weight gradient's kernel has the one name of its own."""
    n, bare = _named_share(lfm2_train_text)
    assert n > 100
    assert len(bare) <= 0.05 * n, (n, bare)
    ops = operations(lfm2_train_text)
    by_phase = {"forward": set(), "recompute": set(), "backward": set()}
    for op, _, path in ops:
        if op not in HEAVY and op != "multiply":
            continue
        phase = ("recompute" if "rematted_computation" in path else
                 "backward" if "transpose(jvp" in path else "forward")
        by_phase[phase].add(part_of(path))
    block = set(profiling.CONV_PARTS) | {
        "qk_norm", "attn_proj", "router", "expert_dispatch", "expert_layer",
        "expert_combine", "grouped_expert_matmul", "mlp"}
    for phase, seen in by_phase.items():
        assert block <= seen, (phase, sorted(block - seen))
    assert "grouped_expert_matmul_dw" in by_phase["backward"]
    assert "grouped_expert_matmul_dw" not in (by_phase["forward"]
                                              | by_phase["recompute"])
    assert {"embed", "head", "loss", "optimizer"} <= by_phase["forward"]
    kernels = [part_of(path) for op, _, path in ops if op == "custom-call"
               and part_of(path) in ("grouped_expert_matmul",
                                     "grouped_expert_matmul_dw")]
    routed = 4
    # three products forward, three recomputed, three rows' gradients
    assert kernels.count("grouped_expert_matmul") == 9 * routed
    assert kernels.count("grouped_expert_matmul_dw") == 3 * routed


def test_the_convolution_parts_are_a_tuple_of_their_own():
    assert profiling.CONV_PARTS == ("conv_proj", "short_conv", "conv_gate")
    assert profiling.EXPERT_GRAD_PARTS == ("grouped_expert_matmul_dw",)
    assert not set(profiling.CONV_PARTS + profiling.EXPERT_GRAD_PARTS) & set(
        profiling.PARTS + profiling.SSM_PARTS + profiling.BLOCK_PARTS
        + profiling.LOOP_PARTS)


def test_a_name_outside_the_vocabulary_raises_at_trace_time():
    def f(x):
        with profiling.part("nonsense"):
            return x + 1

    with pytest.raises(ValueError, match="nonsense"):
        jax.jit(f).lower(jnp.zeros(2))
    assert len(set(VOCABULARY)) == len(VOCABULARY)
    assert profiling.SSM_PARTS == (
        "ssm_proj", "ssm_conv", "ssm_scan", "ssm_update", "ssm_gate_norm",
        "latent_proj")


def test_named_scope_is_spelled_once():
    """``jax.named_scope`` is called in ``util/profiling.part`` and
    nowhere under ``models/`` or ``ops/``."""
    hits = []
    for sub in ("models", "ops"):
        for base, _, files in os.walk(os.path.join(ROOT, "ray_tpu", sub)):
            for name in files:
                if name.endswith(".py"):
                    with open(os.path.join(base, name)) as f:
                        if "named_scope" in f.read():
                            hits.append(os.path.join(base, name))
    assert hits == []


def test_the_platform_is_asked_only_under_ops():
    """Whether a kernel or its XLA oracle runs is the ops' question:
    ``on_tpu`` is named in no file of ``ray_tpu/`` outside
    ``ray_tpu/ops/`` (a model calls an op's dispatcher; each dispatcher
    asks ``ops.attention.on_tpu()``, the one lookup a test steers)."""
    hits = []
    ops = os.path.join(ROOT, "ray_tpu", "ops") + os.sep
    for base, _, files in os.walk(os.path.join(ROOT, "ray_tpu")):
        for name in files:
            path = os.path.join(base, name)
            if name.endswith(".py") and not path.startswith(ops):
                with open(path) as f:
                    if "on_tpu" in f.read():
                        hits.append(path)
    assert hits == []


def test_the_engine_takes_every_program_from_the_serving_model():
    """``serve/llm.py`` imports nothing from the modules that build the
    dense decoder's programs, caches and configs, at any depth of the
    file: what it runs comes through ``models/serving.py``. (Its AST is
    read; the module is not imported.)"""
    import ast

    with open(os.path.join(ROOT, "ray_tpu", "serve", "llm.py")) as f:
        tree = ast.parse(f.read())
    behind = ("decoding", "paged_cache", "llama")
    hits, seen = [], []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names = [f"{node.module}.{a.name}" for a in node.names]
        else:
            continue
        seen += names
        hits += [n for n in names for b in behind
                 if f"ray_tpu.models.{b}." in n + "."]
    assert hits == []
    assert any(n.startswith("ray_tpu.models.serving.") for n in seen)
