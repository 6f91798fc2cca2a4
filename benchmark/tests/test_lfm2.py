"""What PR 57 added to the benchmark as new files: the ``lfm2`` adapter's
counts against hand counts, the configuration's file against the
catalog's published keys and ISSUE 57's cut, the ``pretrain-8k`` mix, the
cell's train step compiled for a described v5e, the adapter's program
against the reference at a tiny size (and the int8 control beside it),
the new metric files, and a rehearsal of a tiny configuration of the
block through ``run.py`` as a train job."""

import json
import math
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark import checks, fold, model_spec, sizing, traffic_gen, weights
from benchmark import run as bench_run

BENCH = model_spec.HERE
ROOT = os.path.dirname(BENCH)
NAME = "lfm2-24b-a2b-ep4-l5"
CELL = "train-moe-conv-8k"
SPEC = model_spec.load_config(NAME)
ARCH = model_spec.adapter(SPEC)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)


def _cell(name=CELL):
    with open(os.path.join(BENCH, "cells", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ counts
def test_parameters_of_the_whole_model_and_of_the_cut():
    h = 2048
    conv, attn = 4 * h * h, 2 * h * h + 2 * h * 512
    expert, dense = 3 * h * 1536, 3 * h * 11776
    assert (conv, attn, 64 * expert, dense) == (
        16_777_216, 10_485_760, 603_979_776, 72_351_744)
    pub = SPEC["published"]
    whole = ARCH.num_params(pub)
    small = (30 * (2 * h + 3 * h) + 10 * (2 * h + 128) + 38 * 64 + h)
    assert whole == (30 * conv + 10 * attn + 38 * (64 * expert + h * 64)
                     + 2 * dense + 65536 * h + small)
    assert round(whole / 1e9, 2) == 23.84
    # the cut: a dense conv layer, a routed attention layer, three routed
    # conv layers, a quarter of the table
    kinds = ARCH.layer_kinds(SPEC)
    assert kinds == [("conv", False), ("full_attention", True),
                     ("conv", True), ("conv", True), ("conv", True)]
    held = model_spec.num_params(SPEC)
    routed = 16 * expert + h * 64 + 64 + 2 * h
    assert held == (conv + 3 * h + dense + 2 * h            # the dense layer
                    + attn + 128 + routed
                    + 3 * (conv + 3 * h + routed)
                    + 16384 * h + h) == 788_052_352
    assert round(8 * held / 1e9, 1) == 6.3 and round(16 * held / 1e9, 1) == 12.6
    mp = model_spec.matrix_params(SPEC)
    assert mp == {"mixers": 4 * conv + attn, "dense": dense,
                  "routers": 4 * h * 64, "experts": 4 * 16 * expert,
                  "experts_a_token": 4.0 * expert, "head": 16384 * h}
    # what a token multiplies: 221.8 M, the experts 17% of it
    touched = (mp["mixers"] + mp["dense"] + mp["routers"]
               + mp["experts_a_token"] + mp["head"])
    assert round(touched / 1e6, 1) == 221.8
    assert 0.16 < mp["experts_a_token"] / touched < 0.18
    flops = model_spec.train_flops_per_token(SPEC, 8192)
    assert flops == 6.0 * touched + 6.0 * 1 * 8192 * 2048
    assert round(flops / 1e9, 2) == 1.43


def test_the_weights_tree_holds_the_cuts_parameters():
    leaves = jax.tree.leaves(ARCH.weight_shapes(SPEC),
                             is_leaf=lambda t: isinstance(t, tuple))
    assert sum(math.prod(s) for s in leaves) == model_spec.num_params(SPEC)
    std, stds = ARCH.weight_stds(SPEC)
    assert std == 2048 ** -0.5
    assert stds["router_bias"] == 0.01 and "router" not in stds
    assert stds["conv_k"] == 3 ** -0.5
    for name in ("w_out", "wo", "w_down", "we_down"):
        assert stds[name] == std / 10 ** 0.5
    tree = ARCH.weight_shapes(SPEC)
    assert isinstance(tree["layers"], list) and len(tree["layers"]) == 5
    assert tree["layers"][1]["wq"] == (2048, 32, 64)
    assert tree["layers"][4]["we_gate"] == (16, 2048, 1536)
    assert tree["layers"][4]["router"] == (2048, 64)
    assert tree["layers"][0]["w_gate"] == (2048, 11776)
    assert tree["embed"] == (16384, 2048) and "lm_head" not in tree


def test_kernel_counts_by_the_kernels_instruction_names():
    sizes = dict(batch=6, seq=8192)
    tri = 6 * 32 * 8192 * 8192 * 64
    assert [model_spec.kernel_counts(SPEC, k, **sizes) for k in (
        "flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv")
    ] == [{"flops": 2 * tri}, {"flops": 3 * tri}, {"flops": 4 * tri}]
    # the rows EXPECTED here: one pair a token
    rows = 6 * 8192 * 4 * 16 / 64
    assert rows == 49152
    for kernel in ("grouped_expert_matmul", "grouped_expert_matmul_dw"):
        assert model_spec.kernel_counts(SPEC, kernel, **sizes) == {
            "flops": 2.0 * rows * 2048 * 1536}
    with pytest.raises(KeyError, match="no kernel named"):
        model_spec.kernel_counts(SPEC, "paged_decode_attention")
    for name, args in (("engine_kwargs", ({},)),
                       ("lower_serve_programs", ({}, None)),
                       ("kv_bytes_per_token", ())):
        with pytest.raises(SystemExit, match="no serving path"):
            getattr(ARCH, name)(SPEC, *args)
    with pytest.raises(SystemExit, match="no serving path"):
        ARCH.serve_program_logits(None, SPEC, None, {}, prefill=1)


# ------------------------------------------------------- the configuration
def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "LFM2-24B-A2B")


def test_the_file_keeps_every_published_key_and_states_its_cut():
    reduced = ["num_hidden_layers", "num_dense_layers", "num_experts",
               "vocab_size"]
    assert SPEC["reduced"] == reduced
    pub = SPEC["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"],
            pub["num_experts"], pub["vocab_size"]) == (40, 2, 64, 65536)
    assert (SPEC["num_hidden_layers"], SPEC["num_dense_layers"],
            SPEC["num_experts"], SPEC["vocab_size"]) == (5, 1, 16, 16384)
    assert (SPEC["layer_first"], SPEC["router_width"],
            SPEC["experts_first"]) == (1, 64, 0)
    for key, value in pub.items():
        if key not in reduced:
            assert SPEC[key] == value, key
    assert len(SPEC["layer_types"]) == 40          # the group stands whole
    assert SPEC["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    row = _catalog()
    assert SPEC["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        assert pub[key] == value, key
        if key not in reduced:
            assert SPEC[key] == value, key
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "conv_L_cache"):
        assert key not in reduced and SPEC[key] == row["config"][key]
    assert {"split_order", "qk_norm", "route_norm_eps", "tied_head",
            "final_norm", "expert_bias", "torch_dtype"} <= set(
                SPEC["assumed"])
    for key in ("split_order", "qk_norm", "route_norm_eps", "tied_head",
                "final_norm", "expert_bias"):
        assert "published modeling code" in SPEC["assumed"][key]
    assert "update" in SPEC["not_run"] and "serving" in SPEC["not_run"]
    assert "four chips share each layer" in SPEC["deployment"]
    entry = [c for c in BENCHMARK["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == reduced and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == row["source_url"]
    cfg = ARCH.program_config(SPEC)
    assert (cfg.hidden, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.moe_dim, cfg.n_experts, cfg.top_k,
            cfg.experts_held, cfg.vocab_size, cfg.conv_taps) == (
        2048, 32, 8, 64, 11776, 1536, 64, 4, (0, 16), 16384, 3)
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv") and cfg.num_dense_layers == 1
    assert cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-5


def test_a_configuration_that_is_not_this_block_exits_by_name(monkeypatch):
    with pytest.raises(SystemExit, match="needs the keys"):
        ARCH.check_config({k: v for k, v in SPEC.items()
                           if k != "conv_L_cache"})
    with pytest.raises(SystemExit, match="no convolution bias"):
        ARCH.check_config(dict(SPEC, conv_bias=True))
    with pytest.raises(SystemExit, match="outside the model"):
        ARCH.check_config(dict(SPEC, experts_first=56))
    with pytest.raises(SystemExit, match="not 23.84 B"):
        ARCH.check_config(dict(SPEC, published=dict(
            SPEC["published"], num_experts=32)))
    # a checkout from before the block: refused in the driver, by the
    # module's path, before anything is started
    monkeypatch.setattr(os.path, "isfile",
                        lambda p: not p.endswith("models/lfm2.py"))
    with pytest.raises(SystemExit, match=r"no file .*ray_tpu/models/lfm2"):
        ARCH.check_config(SPEC)


def test_the_limits_fail_the_control_and_pass_the_program():
    lim = model_spec.limits(SPEC)
    assert set(lim) == {"train_tail_grad_rel_err", "loss_fall_min"}
    grad = lim["train_tail_grad_rel_err"]
    assert grad["program_largest"] < grad["limit"] < \
        grad["control_int8_smallest"]
    assert grad["seeds"] >= 12 and grad["control_seeds"] >= 12
    fall = lim["loss_fall_min"]
    assert fall["control_lr0_largest"] < fall["limit"] < \
        fall["program_smallest"]
    # more room on the program's side, and said to be under three
    assert 2.5 * fall["limit"] <= fall["program_smallest"]
    assert 1.3 * fall["control_lr0_largest"] <= fall["limit"]
    assert "NOT THE CONTRACT'S THREE" in fall["why"]


# ---------------------------------------------------- the cell and its lists
NEW = ("grouped_expert_matmul_train_roofline", "train_experts_dev_pct",
       "train_expert_glue_dev_pct", "train_short_conv_dev_pct",
       "train_optimizer_dev_pct")
# the train cells' accepted readings: the cell joins their lists (what
# ``fold.py`` makes of the tagged copies a cell's PR would bring)
SHARED = ("train_mfu", "flash_roofline", "flash_fwd_dev_ms",
          "train_recompute_dev_pct", "train_unnamed_dev_pct",
          "device_idle_pct", "trainer_start_s")


def _parts(name):
    with open(os.path.join(BENCH, "layer_metrics", "parts",
                           name + ".json")) as f:
        return json.load(f)["parts"]


def _mine():
    return [m for m in BENCHMARK["per_layer"]
            if CELL in m.get("workloads", ())]


def test_the_cell_and_the_lists_it_joins():
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    assert cells[CELL] == dict(cells[CELL], config=NAME, chips=1,
                               traffic="pretrain-8k")
    assert len(cells[CELL]["why"]) <= 200
    lists = {m["name"]: m.get("workloads")
             for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]}
    assert CELL in lists["train_tokens_per_s"]
    for name in ("compiles_in_window", "peak_hbm_gb"):
        assert lists[name] is None
    assert len(_mine()) == 12
    assert {m["moves"] for m in _mine()} == {"train_tokens_per_s", "setup_s"}
    from ray_tpu.util import profiling

    assert tuple(_parts("lfm2")) == profiling.CONV_PARTS
    assert tuple(_parts("expert_grad")) == profiling.EXPERT_GRAD_PARTS
    known = {"unnamed", *_parts("base"), *_parts("lfm2"),
             *_parts("expert_grad"), "qk_norm"}
    for m in _mine():
        reader, args = fold.resolved(ROOT, m["name"])
        if reader == "_dev_ms_by_part":
            assert args["program"] == "^jit_train_step"
            assert set(args.get("parts", ())) <= known, m["name"]
    job = _cell()["job"]
    assert job["batch"] == 6 and "7" in _cell()["how_found"]["batch"]


@pytest.mark.parametrize("name", NEW)
def test_the_cell_reports_an_entry_of_its_own_mechanism(name):
    assert [m for m in _mine() if m["name"] == name], name
    assert callable(bench_run.load_reader(name))


@pytest.mark.parametrize("base", SHARED)
def test_the_cell_is_in_the_list_of_a_shared_entry(base):
    """Under whatever tag, once, and beside the two train cells that
    stand: the same reader, the same arguments."""
    mine = [m for m in _mine() if m["name"].split(".")[0] == base]
    assert len(mine) == 1, base
    assert {"train-1chip", "train-fsdp2tp2"} <= set(mine[0]["workloads"])
    assert fold.groups(ROOT, BENCHMARK["per_layer"]) == []


def test_pretrain_8k_is_a_train_job_of_fresh_batches_over_the_slice():
    mix = traffic_gen.load_mix("pretrain-8k")
    assert (mix["kind"], mix["seq"], mix["tokens"]) == (
        "train_job", 8192, "uniform")
    assert mix["optimizer"] == traffic_gen.load_mix(
        "pretrain-4k")["optimizer"] == {"warmup_steps": 32}
    for seed in (1, 3_000_000_000):
        a = traffic_gen.train_batches(mix, seed, SPEC["vocab_size"], 2)
        b = traffic_gen.train_batches(mix, seed, SPEC["vocab_size"], 2)
        first, second = next(a), next(a)
        assert first.shape == (2, 8192) and first.dtype.name == "int32"
        assert 0 <= first.min() and first.max() < 16384
        assert (first == next(b)).all() and (first != second).any()


# ------------------------------------------------- the described v5e compile
@pytest.fixture(scope="module")
def devices():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return topo.devices


def test_the_cells_train_step_fits_one_chip(devices, monkeypatch):
    """The step at the cell's batch of 8,192-token sequences: every
    kernel of the routed layer's forward AND backward is in the program
    (9 grouped products a routed layer: three forward, three recomputed,
    three for the rows' gradient; 3 ``dw``), the flash kernels at a head
    of 64, the combine's kernel in runs of tokens; the train state is
    donated; and the sum fits the 15.75 GiB the compiler allows with the
    room the cell's ``how_found`` states."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    job = dict(_cell()["job"], seq=8192)
    lowered = sizing.train_program(SPEC, job, devices[:1], job["batch"])
    compiled = lowered.compile()
    calls = sizing.kernel_calls(compiled.as_text())
    assert calls["grouped_expert_matmul"] == 4 * 9
    assert calls["grouped_expert_matmul_dw"] == 4 * 3
    assert calls["flash_attention_fwd"] == 2
    assert calls["flash_attention_dq"] == calls["flash_attention_dkv"] == 1
    assert calls["expert_combine"] >= 4
    mem = compiled.memory_analysis()
    state = 6 * model_spec.num_params(SPEC)      # weights and two moments
    assert mem.argument_size_in_bytes >= state
    assert mem.alias_size_in_bytes >= state
    total = sizing.total_bytes(mem)
    assert 0.85 * sizing.HBM_BYTES < total < 15.0 * 1024 ** 3    # 14.07 GiB


# -------------------------------------------- program, reference and control
TINY = dict(
    SPEC, name="tiny-lfm2", limits="benchmark/limits/tiny-lfm2.json",
    vocab_size=256, hidden_size=64, intermediate_size=160,
    moe_intermediate_size=96, num_attention_heads=4, num_key_value_heads=2,
    num_experts=2, router_width=8, experts_first=2, num_experts_per_tok=2,
    max_position_embeddings=1024)
TINY.pop("published")


def test_the_adapters_program_against_the_reference_at_a_tiny_size():
    """``checks.train_check``'s two sides on seeded bfloat16 weights:
    the program's tail gradients (the last routed conv layer, the final
    norm, the tied table as the head reads it) within bfloat16 of the
    float32 reference's, the int8 control further away; and the trees
    agree leaf for leaf."""
    params = weights.make(TINY, 11)
    tokens = checks.sample_tokens(TINY, 11, 48)
    ref = model_spec.reference(TINY)
    want_loss, want = ref.last_block_loss_and_grads(params, tokens, TINY)
    loss, got = model_spec.adapter(TINY).train_program_loss_and_grads(
        params, TINY, tokens)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
    assert set(got) == {"layer", "final_norm", "lm_head"}
    assert got["lm_head"].shape == (64, 256)
    assert set(got["layer"]) >= {"w_in", "conv_k", "w_out", "router",
                                 "router_bias", "we_gate", "we_down"}
    assert abs(float(loss) - float(want_loss)) < 0.02 * float(want_loss)
    err = ref.rel_err(checks._flat(got), checks._flat(want))
    _, control = ref.last_block_loss_and_grads(params, tokens, TINY,
                                               quant="int8")
    control_err = ref.rel_err(checks._flat(control), checks._flat(want))
    assert err < 0.06 < control_err, (err, control_err)
    assert not jnp.any(got["layer"]["router_bias"])
    assert not jnp.any(want["layer"]["router_bias"])


# ------------------------------------------------------------- a rehearsal
TRAIN_METRICS = ("train_mfu", "trainer_start_s", "compiles_in_window")


def test_a_tiny_configuration_of_the_block_trains_through_the_harness(
        tmp_path):
    """On the CPU (pretend chip, nothing it prints is a measurement):
    the adapter's ``train_setup``, the reference check, ``JaxTrainer``,
    the window and the cell's host-side metrics work end to end through
    ``run.py``; the readers of kernels and of the device's parts find
    what a CPU capture holds or nothing, and raise nothing. Then a
    checkout WITHOUT the program's module: the driver refuses the cell
    before it starts anything."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "ray_tpu"), tmp_path / "ray_tpu")
    bench = json.loads(json.dumps(BENCHMARK))

    def put(rel, obj):
        (tmp_path / "benchmark" / rel).write_text(json.dumps(obj))

    put("configs/tiny-lfm2.json", TINY)
    put("limits/tiny-lfm2.json", {"limits": {
        "train_tail_grad_rel_err": {"limit": 0.1},
        "loss_fall_min": {"limit": -1.0}}})
    put("cells/tiny-cell.json", {"job": {"batch": 2, "warmup_steps": 1,
                                         "trace_steps": 2}})
    put("traffic/tiny-mix.json", {
        "kind": "train_job", "seq": 64, "tokens": "uniform",
        "optimizer": {"warmup_steps": 2, "learning_rate": 3e-3}})
    bench["configs"].append({
        "name": "tiny-lfm2", "source": "test",
        "file": "benchmark/configs/tiny-lfm2.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-cell", "config": "tiny-lfm2", "traffic": "tiny-mix",
        "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny-cell",
         "--seed", "2147483999", "--seconds", "3", "--trace", "1",
         "--rehearse"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["compared"]["losses_not_finite"]["value"] == 0
    assert line["compared"]["train_tail_grad_rel_err"]["value"] < 0.1
    got = line["metrics"]
    assert set(TRAIN_METRICS) <= set(got), sorted(got)
    assert "[cleanup]" in proc.stdout + proc.stderr
    os.remove(tmp_path / "ray_tpu")
    os.makedirs(tmp_path / "ray_tpu" / "models")
    gone = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny-cell",
         "--seed", "1", "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=60)
    assert gone.returncode != 0
    assert "no file" in gone.stderr and "lfm2.py" in gone.stderr
    assert "bringing up" not in gone.stdout
