"""What PR 27 added to the benchmark as new files: the ``mimo_v2``
adapter's counts against hand counts, the configuration's file against
its published keys, the ``reasoning-decode`` mix, the cell's programs
compiled for a described v5e, and a rehearsal of a tiny configuration of
the block through ``run.py`` with the cell's per-layer metrics."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from benchmark import model_spec, sizing, traffic_gen

BENCH = model_spec.HERE
ROOT = os.path.dirname(BENCH)
NAME = "mimo-v2.5-ep16-l7"
CELL = "serve-moe-window-decode"
SPEC = model_spec.load_config(NAME)
ARCH = model_spec.adapter(SPEC)
UNCUT = {k: v for k, v in {**SPEC, **SPEC["published"]}.items()
         if k != "router_width"}


def _cell():
    with open(os.path.join(BENCH, "cells", CELL + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ counts
def test_parameters_of_the_uncut_model_and_of_the_share():
    full = 4096 * 64 * 192 + 4096 * 4 * (192 + 128) + 64 * 128 * 4096
    window = 4096 * 64 * 192 + 4096 * 8 * (192 + 128) + 64 * 128 * 4096
    assert ARCH.attention_params(SPEC, "full") == full == 89_128_960
    assert ARCH.attention_params(SPEC, "window") == window == 94_371_840
    assert ARCH.expert_params(SPEC) == 3 * 4096 * 2048 == 25_165_824
    assert abs(model_spec.num_params(UNCUT) / 308.7e9 - 1) < 1e-3
    layer0 = full + 3 * 4096 * 16384 + 2 * 4096
    routed = 16 * 25_165_824 + 4096 * 256 + 256 + 2 * 4096
    share = (layer0 + 2 * 19072 * 4096 + 4096
             + 5 * (window + 64 + routed) + (full + routed))
    assert model_spec.num_params(SPEC) == share
    assert round(share / 1e9, 2) == 3.43
    mp = model_spec.matrix_params(SPEC)
    assert mp["experts"] == 6 * 16 * 25_165_824
    assert mp["attention"] == 2 * full + 5 * window
    assert ARCH.layer_kinds(SPEC) == ["full", "window", "window", "window",
                                      "window", "full", "window"]
    assert ARCH.layer_kinds(UNCUT).count("full") == 9


def test_kv_bytes_by_layer_kind():
    assert model_spec.kv_bytes_per_token(SPEC) == 4 * (192 + 128) * 2
    assert model_spec.kv_bytes_per_token(SPEC, kind="window") == 5120
    # the cell's pools: two full layers keep everything, five window
    # layers three blocks a slot
    dep = _cell()["deployment"]
    assert dep["kv_pool_tokens"] * 2 * 2560 == 1_677_721_600
    assert 128 * 3 * 64 * 5 * 5120 == 629_145_600


def test_kernel_counts_by_the_kernels_instruction_names():
    qo = 128 * 64 * (192 + 128) * 2
    assert model_spec.kernel_counts(
        SPEC, "paged_hybrid_decode_full", live_tokens=200_000,
        slots=128) == {"bytes": 200_000 * 2560 + qo}
    assert model_spec.kernel_counts(
        SPEC, "paged_hybrid_decode_window", live_tokens=128 * 128,
        slots=128) == {"bytes": 128 * 128 * 5120 + qo}
    # 1000 routed-layer calls that hit 15.5 experts and computed 64
    # pairs on average
    want = 15.5 * 4096 * 2048 * 2 + 64 * (4096 + 2048) * 2
    assert model_spec.kernel_counts(
        SPEC, "grouped_expert_matmul", experts_hit=15_500, pairs=64_000,
        layer_calls=1000, prefill_experts_hit=1, prefill_pairs=1,
        prefill_layer_calls=1) == {"bytes": want}
    assert model_spec.kernel_counts(
        SPEC, "grouped_expert_matmul_prefill", experts_hit=0, pairs=0,
        layer_calls=0, prefill_experts_hit=32, prefill_pairs=700,
        prefill_layer_calls=2) == {
        "bytes": 16 * 4096 * 2048 * 2 + 350 * 6144 * 2}
    with pytest.raises(KeyError, match="no kernel named"):
        model_spec.kernel_counts(SPEC, "paged_decode_attention")
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
            SPEC["vocab_size"]) == (7, 16, 19072)
    assert SPEC["router_width"] == pub["n_routed_experts"] == 256
    assert pub["vocab_size"] == 8 * SPEC["vocab_size"]
    widths = dict(hidden_size=4096, num_attention_heads=64,
                  num_key_value_heads=4, swa_num_key_value_heads=8,
                  head_dim=192, v_head_dim=128, sliding_window=128,
                  moe_intermediate_size=2048, num_experts_per_tok=8,
                  intermediate_size=16384)
    assert {k: SPEC[k] for k in widths} == widths
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == SPEC["reduced"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert "16 chips share each layer" in SPEC["deployment"]
    cfg = ARCH.program_kwargs(SPEC)
    assert cfg["rotary_dim"] == 64 and cfg["experts_held"] == (0, 16)
    assert cfg["layer_kinds"] == (0, 1, 1, 1, 1, 0, 1)
    assert cfg["moe_layers"] == (0, 1, 1, 1, 1, 1, 1)


def test_the_weights_tree_holds_the_shares_parameters():
    import math

    leaves = jax.tree.leaves(ARCH.weight_shapes(SPEC),
                             is_leaf=lambda t: isinstance(t, tuple))
    assert sum(math.prod(s) for s in leaves) == model_spec.num_params(SPEC)


# ------------------------------------------------------------- the traffic
def test_reasoning_decode_sends_the_same_lengths_for_every_seed():
    mix = traffic_gen.load_mix("reasoning-decode")
    assert mix["kind"] == "closed_loop_handle" and mix["clients"] == 256
    dep = _cell()["deployment"]
    assert mix["clients"] == 2 * dep["num_slots"]
    shapes = []
    for seed in (1, 2_147_483_999, 3_000_000_000):
        stream = traffic_gen.request_stream(mix, seed, SPEC["vocab_size"])
        reqs = [next(stream) for _ in range(512)]
        assert all(0 <= t < 19072 for r in reqs for t in r["prompt"])
        shapes.append([(len(r["prompt"]), r["max_tokens"]) for r in reqs])
    assert shapes[0] == shapes[1] == shapes[2]
    plens = [p for p, _ in shapes[0]]
    olens = [o for _, o in shapes[0]]
    assert min(plens) >= 256 and max(plens) <= 1024
    assert min(olens) >= 768 and max(olens) <= 1536
    assert max(p + o for p, o in shapes[0]) <= dep["max_seq"]
    assert traffic_gen.prompt_buckets(mix) == [512, 1024]
    # nothing is preempted: every slot at its longest fits the full pool
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
    """The decode step at 128 slots and the 1024 prefill bucket: both
    kernels are there, the pools are updated in place (the temporaries
    are a small part of ONE pool), and the sum fits."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dep = _cell()["deployment"]
    decode, bucket = sizing.serve_programs(SPEC, dep, device)
    compiled = decode.compile()
    text = compiled.as_text()
    # each kernel by name, and a call a layer that has it at the least
    # (how many a layer makes is the program's: three products, and since
    # PR 50 the combine)
    calls = sizing.kernel_calls(text)
    window = sum(SPEC["hybrid_layer_pattern"][:SPEC["num_hidden_layers"]])
    assert calls["paged_hybrid_decode_window"] >= window == 5
    assert calls["paged_hybrid_decode_full"] >= 7 - window
    assert calls["grouped_expert_matmul"] >= sum(
        SPEC["moe_layer_freq"][:SPEC["num_hidden_layers"]]) == 6
    mem = compiled.memory_analysis()
    assert sizing.total_bytes(mem) < sizing.HBM_BYTES
    full_pool = dep["kv_pool_tokens"] * 2 * model_spec.kv_bytes_per_token(SPEC)
    window_pool = 128 * 3 * 64 * 5 * model_spec.kv_bytes_per_token(
        SPEC, kind="window")
    weights_and_pools = (2 * model_spec.num_params(SPEC) + full_pool
                         + window_pool)
    assert mem.argument_size_in_bytes >= weights_and_pools
    assert mem.alias_size_in_bytes >= full_pool + window_pool
    assert mem.temp_size_in_bytes < window_pool / 4    # no pool-shaped copy
    largest = max(traffic_gen.prompt_buckets(
        traffic_gen.load_mix("reasoning-decode")))
    assert largest == 1024
    pre = bucket(largest).compile()
    assert "grouped_expert_matmul_prefill" in pre.as_text()
    mem = pre.memory_analysis()
    assert sizing.total_bytes(mem) < sizing.HBM_BYTES
    assert mem.alias_size_in_bytes >= full_pool + window_pool
    assert mem.temp_size_in_bytes < window_pool


# ------------------------------------------------------------- a rehearsal
TINY = dict(
    name="tiny-mimo", source="test", architecture="mimo_v2",
    reference="benchmark/reference/mimo_v2.py",
    limits="benchmark/limits/tiny-mimo.json",
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, swa_num_key_value_heads=4,
    head_dim=24, v_head_dim=16, partial_rotary_factor=0.334,
    rope_theta=1e7, swa_rope_theta=1e4, sliding_window=16,
    attention_value_scale=0.707, hybrid_layer_pattern=[0, 1, 1, 0],
    moe_layer_freq=[0, 1, 1, 1], intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=4, router_width=16,
    experts_first=4, num_experts_per_tok=4, layernorm_epsilon=1e-5,
    max_position_embeddings=1024, tie_word_embeddings=False,
    routed_scaling_factor=None, torch_dtype="bfloat16", reduced=[])
MOE_METRICS = ("expert_pairs_per_step", "expert_load_max_over_mean",
               "expert_pairs_dropped", "window_pool_live_pct",
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

    put("configs/tiny-mimo.json", TINY)
    put("limits/tiny-mimo.json", {"limits": {
        "serve_prefill_logits_rel_err": {"limit": 0.1},
        "serve_decode_logits_rel_err": {"limit": 0.1}}})
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
        "name": "tiny-mimo", "source": "test",
        "file": "benchmark/configs/tiny-mimo.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-cell", "config": "tiny-mimo", "traffic": "tiny-mix",
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
    assert set(MOE_METRICS) <= set(got), sorted(got)
    assert got["expert_pairs_dropped"]["value"] == 0
    # 3 slots x 4 choices x 4 of 16 experts held: 3 pairs a full step
    assert 0 < got["expert_pairs_per_step"]["value"] <= 12
    assert 0 < got["window_pool_live_pct"]["value"] <= 100
    assert "grouped_expert_matmul_roofline" not in got     # no kernel here
    assert "read router_choices_flipped_by_bf16_activations" in proc.stdout
