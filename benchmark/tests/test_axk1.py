"""What PR 31 added to the benchmark as new files: the ``axk1`` adapter's
counts against hand counts, the configuration's file against its
published keys, the ``reasoning-long-decode`` mix, the cell's programs
compiled for a described v5e, the four-chip train cell's file against
the job the compile test compiles, and a rehearsal of a tiny
configuration of the block through ``run.py`` with the cell's per-layer
metrics."""

import json
import math
import os
import shutil
import subprocess
import sys

import jax
import pytest

from benchmark import model_spec, sizing, traffic_gen

BENCH = model_spec.HERE
ROOT = os.path.dirname(BENCH)
NAME = "axk1-ep16-l5"
CELL = "serve-mla-moe-decode"
SPEC = model_spec.load_config(NAME)
ARCH = model_spec.adapter(SPEC)
UNCUT = {k: v for k, v in {**SPEC, **SPEC["published"]}.items()
         if k != "router_width"}


def _cell(name=CELL):
    with open(os.path.join(BENCH, "cells", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ counts
def test_parameters_of_the_uncut_model_and_of_the_share():
    attention = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
                 + 512 * 64 * 256 + 64 * 128 * 7168)
    assert ARCH.attention_params(SPEC) == attention == 101_122_048
    assert ARCH.expert_params(SPEC) == 3 * 7168 * 2048 == 44_040_192
    assert abs(model_spec.num_params(UNCUT) / 518.98e9 - 1) < 1e-3
    norms = 2 * 7168 + 1536 + 512
    layer0 = attention + 3 * 7168 * 18432 + norms
    routed = attention + 13 * 44_040_192 + 7168 * 192 + norms
    share = layer0 + 4 * routed + 2 * 20480 * 7168 + 7168
    assert model_spec.num_params(SPEC) == share
    assert round(share / 1e9, 3) == 3.491
    mp = model_spec.matrix_params(SPEC)
    assert mp["experts"] == 4 * 12 * 44_040_192
    assert mp["shared_experts"] == 4 * 44_040_192
    assert mp["router"] == 4 * 7168 * 192
    assert model_spec.matrix_params(UNCUT)["experts"] == 60 * 192 * 44_040_192


def test_a_cached_token_is_one_latent_row_a_layer():
    assert ARCH.row_width(SPEC) == 576
    assert model_spec.kv_bytes_per_token(SPEC) == 5 * 1152 == 5760
    # against the expanded keys and values of 64 heads
    assert 64 * (192 + 128) * 2 // 1152 == 35


def test_kernel_counts_by_the_kernels_instruction_names():
    got = model_spec.kernel_counts(SPEC, "paged_mla_decode",
                                   live_tokens=442_000, slots=192)
    assert got == {
        "bytes": 442_000 * 1152 + 192 * 64 * (576 + 512) * 2,
        "flops": 442_000 * 64 * (576 + 512) * 2}
    assert round(got["flops"] / (442_000 * 1152), 1) == 120.9
    want = 11.5 * 7168 * 2048 * 2 + 96 * (7168 + 2048) * 2
    assert model_spec.kernel_counts(
        SPEC, "grouped_expert_matmul", experts_hit=11_500, pairs=96_000,
        layer_calls=1000, prefill_experts_hit=1, prefill_pairs=1,
        prefill_layer_calls=1) == {"bytes": want}
    assert model_spec.kernel_counts(
        SPEC, "grouped_expert_matmul_prefill", experts_hit=0, pairs=0,
        layer_calls=0, prefill_experts_hit=24, prefill_pairs=1400,
        prefill_layer_calls=2) == {
        "bytes": 12 * 7168 * 2048 * 2 + 700 * 9216 * 2}
    with pytest.raises(KeyError, match="no kernel named"):
        model_spec.kernel_counts(SPEC, "paged_hybrid_decode_full")
    for name, args in (("train_flops_per_token", (4096,)),
                       ("train_setup", (None, None)),
                       ("train_program_loss_and_grads", (None, None))):
        with pytest.raises(SystemExit, match="no train path"):
            getattr(ARCH, name)(SPEC, *args)


# ------------------------------------------------------- the configuration
def test_the_file_keeps_every_published_key_but_the_reduced():
    pub = SPEC["published"]
    assert SPEC["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    for key, value in pub.items():
        if key in SPEC["reduced"]:
            assert SPEC[key] != value, key
        else:
            assert SPEC[key] == value, key
    assert (SPEC["num_hidden_layers"], SPEC["n_routed_experts"],
            SPEC["vocab_size"]) == (5, 12, 20480)
    assert SPEC["router_width"] == pub["n_routed_experts"] == 192
    assert pub["vocab_size"] == 8 * SPEC["vocab_size"]
    assert pub["n_routed_experts"] == 16 * SPEC["n_routed_experts"]
    widths = dict(hidden_size=7168, num_attention_heads=64,
                  q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128,
                  moe_intermediate_size=2048, n_shared_experts=1,
                  intermediate_size=18432, n_group=8, topk_group=4,
                  num_experts_per_tok=8, routed_scaling_factor=2.5)
    assert {k: SPEC[k] for k in widths} == widths
    assert SPEC["rope_scaling"] == pub["rope_scaling"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == SPEC["reduced"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert "16 chips share each layer" in SPEC["deployment"]
    cfg = ARCH.program_kwargs(SPEC)
    assert cfg["experts_held"] == (0, 12) and cfg["n_experts"] == 192
    assert (cfg["n_group"], cfg["topk_group"], cfg["top_k"]) == (8, 4, 8)
    assert cfg["yarn"]["factor"] == 32 and cfg["dense_layers"] == 1


def test_the_weights_tree_holds_the_shares_parameters():
    leaves = jax.tree.leaves(ARCH.weight_shapes(SPEC),
                             is_leaf=lambda t: isinstance(t, tuple))
    assert sum(math.prod(s) for s in leaves) == model_spec.num_params(SPEC)


# ------------------------------------------------------------- the traffic
def test_reasoning_long_decode_sends_the_same_lengths_for_every_seed():
    mix = traffic_gen.load_mix("reasoning-long-decode")
    assert mix["kind"] == "closed_loop_handle" and mix["clients"] == 384
    dep = _cell()["deployment"]
    assert mix["clients"] == 2 * dep["num_slots"]
    shapes = []
    for seed in (1, 2_147_483_999, 3_000_000_000):
        stream = traffic_gen.request_stream(mix, seed, SPEC["vocab_size"])
        reqs = [next(stream) for _ in range(768)]
        assert all(0 <= t < 20480 for r in reqs for t in r["prompt"])
        shapes.append([(len(r["prompt"]), r["max_tokens"]) for r in reqs])
    assert shapes[0] == shapes[1] == shapes[2]
    plens = [p for p, _ in shapes[0]]
    olens = [o for _, o in shapes[0]]
    assert min(plens) >= 1024 and max(plens) <= 2048
    assert min(olens) >= 768 and max(olens) <= 2304
    assert max(p + o for p, o in shapes[0]) <= dep["max_seq"]
    assert traffic_gen.prompt_buckets(mix) == [2048]
    # nothing is preempted: every slot at its longest fits the pool
    assert dep["kv_pool_tokens"] == dep["num_slots"] * dep["max_seq"]


# ------------------------------------------------- the described v5e compile
@pytest.fixture(scope="module")
def device():
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
    return topo.devices[0]


def test_the_cells_programs_fit_one_chip(device, monkeypatch):
    """The decode step at 192 slots and the 2048 prefill bucket: the
    latent kernel once a layer and the expert products are there, the
    pool (a row padded to 640 lanes) is updated in place (the
    temporaries are a small part of it), and the sum fits."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dep = _cell()["deployment"]
    decode, bucket = sizing.serve_programs(SPEC, dep, device)
    compiled = decode.compile()
    text = compiled.as_text()
    # each kernel by name, and a call a layer that has it at the least
    # (how many a layer makes is the program's: three products, and since
    # PR 50 the combine)
    calls = sizing.kernel_calls(text)
    assert calls["paged_mla_decode"] >= SPEC["num_hidden_layers"] == 5
    assert calls["grouped_expert_matmul"] >= 5 - SPEC["first_k_dense_replace"]
    mem = compiled.memory_analysis()
    assert sizing.total_bytes(mem) < sizing.HBM_BYTES
    pool = (1 + dep["kv_pool_tokens"] // 64) * 64 * 5 * 640 * 2
    assert pool >= dep["kv_pool_tokens"] * model_spec.kv_bytes_per_token(SPEC)
    assert mem.argument_size_in_bytes >= 2 * model_spec.num_params(SPEC) + pool
    assert mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < pool / 50           # no pool-shaped copy
    assert traffic_gen.prompt_buckets(
        traffic_gen.load_mix("reasoning-long-decode")) == [2048]
    pre = bucket(2048).compile()
    assert "grouped_expert_matmul_prefill" in pre.as_text()
    mem = pre.memory_analysis()
    assert sizing.total_bytes(mem) < sizing.HBM_BYTES
    assert mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < pool / 3


def test_the_four_chip_cell_is_the_job_the_compile_test_compiles():
    from benchmark.tests import test_chip_compile

    job = _cell("train-fsdp2tp2")["job"]
    assert {k: job[k] for k in ("batch", "fsdp", "tp")} \
        == test_chip_compile.FSDP2TP2
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells["train-fsdp2tp2"]["chips"] == 4
    assert cells["train-fsdp2tp2"]["config"] == "mistral-7b-l16"
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == ["train-fsdp2tp2"]
    lists = {m["name"]: m.get("workloads", [])
             for m in bench["end_to_end"] + bench["per_layer"]}
    for name in ("train_tokens_per_s", "train_mfu", "flash_roofline",
                 "trainer_start_s", "device_idle_pct.train",
                 "collective_exposed_pct"):
        assert "train-fsdp2tp2" in lists[name], name


def test_collective_exposed_pct_reads_the_collectives_share():
    reader = model_spec.load_module(os.path.join(
        BENCH, "layer_metrics", "collective_exposed_pct.py")).read
    ops = {"fusion.1": [3, 0.6, "fusion", ""],
           "all-gather-start.2": [3, 0.01, "all-gather-start", ""],
           "all-gather-done.2": [3, 0.09, "all-gather-done", ""],
           "all-reduce.7": [3, 0.1, "all-reduce", ""],
           "copy.3": [3, 0.2, "copy", ""]}
    assert reader({"trace": {"ops": ops, "busy_s": 1.0}}) \
        == pytest.approx(20.0)
    del ops["all-reduce.7"], ops["all-gather-start.2"], \
        ops["all-gather-done.2"]
    assert reader({"trace": {"ops": ops, "busy_s": 0.8}}) is None


# ------------------------------------------------------------- a rehearsal
TINY = dict(
    SPEC, name="tiny-axk1", source="test",
    limits="benchmark/limits/tiny-axk1.json",
    vocab_size=256, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
    router_width=32, experts_first=8, max_position_embeddings=1024,
    rope_scaling=dict(SPEC["rope_scaling"], factor=4,
                      original_max_position_embeddings=64), reduced=[])
MLA_METRICS = ("expert_pairs_per_step", "expert_load_max_over_mean",
               "expert_pairs_dropped", "latent_pool_live_pct",
               "engine_step_ms", "slot_occupancy_pct")


def test_a_tiny_configuration_of_the_block_runs_through_the_harness(
        tmp_path):
    """On the CPU (pretend chip, nothing it prints is a measurement):
    the adapter, the reference, the check and the cell's counter metrics
    work end to end through ``run.py``; the readers of kernels find no
    kernel here and leave their metrics out without raising."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "ray_tpu"), tmp_path / "ray_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def put(rel, obj):
        (tmp_path / "benchmark" / rel).write_text(json.dumps(obj))

    put("configs/tiny-axk1.json", TINY)
    put("limits/tiny-axk1.json", {"limits": {
        "serve_prefill_logits_rel_err": {"limit": 0.15},
        "serve_decode_logits_rel_err": {"limit": 0.15}}})
    put("cells/tiny-cell.json", {"deployment": {
        "num_slots": 3, "max_seq": 512, "kv_block_size": 64,
        "kv_pool_tokens": 1536, "max_ongoing_requests": 16}})
    put("traffic/tiny-mix.json", {
        "kind": "closed_loop_handle", "clients": 6, "block": 16,
        "prompt_len": {"dist": "uniform", "min": 40, "max": 100},
        "output_len": {"dist": "fixed", "value": 6, "min": 6, "max": 6},
        "temperature": 0.0, "lead_s": 1.0, "drain_s": 30.0,
        "trace_offset_s": 0.5, "trace_s": 1.0})
    bench["configs"].append({
        "name": "tiny-axk1", "source": "test",
        "file": "benchmark/configs/tiny-axk1.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-cell", "config": "tiny-axk1", "traffic": "tiny-mix",
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
    got = line["metrics"]
    assert set(MLA_METRICS) <= set(got), sorted(got)
    assert got["expert_pairs_dropped"]["value"] == 0
    # 3 slots x 8 choices x 8 of 32 experts held: 6 pairs a full step
    assert 0 < got["expert_pairs_per_step"]["value"] <= 24
    assert 0 < got["latent_pool_live_pct"]["value"] <= 100
    assert "paged_mla_decode_roofline" not in got          # no kernel here
    assert "read router_choices_flipped_by_bf16_activations" in proc.stdout
