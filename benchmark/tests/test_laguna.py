"""What PR 33 added to the benchmark as new files: the ``laguna`` adapter's
counts against hand counts, the configuration's file against its
published keys, the ``agent-mixed-decode`` mix, the cell's programs
compiled for a described v5e, the ``experts_hit_pct`` reader, and a
rehearsal of a tiny configuration of the block through ``run.py`` with
the cell's per-layer metrics."""

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
NAME = "laguna-xs2-l5"
CELL = "serve-moe-whole-mixed-decode"
SPEC = model_spec.load_config(NAME)
ARCH = model_spec.adapter(SPEC)
UNCUT = dict(SPEC["published"], architecture="laguna")


def _cell(name=CELL):
    with open(os.path.join(BENCH, "cells", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ counts
def test_parameters_of_the_uncut_model_and_of_the_cut():
    full = 2048 * 48 * 128 * 2 + 2 * 2048 * 8 * 128 + 2048 * 48
    window = 2048 * 64 * 128 * 2 + 2 * 2048 * 8 * 128 + 2048 * 64
    assert ARCH.attention_params(SPEC, 48) == full == 29_458_432
    assert ARCH.attention_params(SPEC, 64) == window == 37_879_808
    assert ARCH.expert_params(SPEC) == 3 * 2048 * 512 == 3_145_728
    assert abs(model_spec.num_params(UNCUT) / 33.44e9 - 1) < 1e-3
    assert round(ARCH.active_params(UNCUT) / 1e9, 2) == 3.02
    norms = 2 * 2048
    routed = 257 * 3_145_728 + 2048 * 256 + norms
    layer0 = full + 3 * 2048 * 8192 + norms
    cut = (layer0 + 3 * (window + routed) + (full + routed)
           + 2 * 100352 * 2048 + 2048)
    assert model_spec.num_params(SPEC) == cut
    assert round(cut / 1e9, 3) == 3.870
    mp = model_spec.matrix_params(SPEC)
    assert mp["experts"] == 4 * 256 * 3_145_728
    assert mp["shared_experts"] == 4 * 3_145_728
    assert mp["router"] == 4 * 2048 * 256
    assert mp["dense_mlp"] == 3 * 2048 * 8192
    assert model_spec.matrix_params(UNCUT)["experts"] == 39 * 256 * 3_145_728
    assert ARCH.heads_of(SPEC, "full") == 48
    assert ARCH.heads_of(SPEC, "window") == 64


def test_a_cached_token_and_a_slots_window():
    assert ARCH.layer_kinds(SPEC) == ["full", "window", "window", "window",
                                      "full"]
    assert model_spec.kv_bytes_per_token(SPEC) == 2 * 4096 == 8192
    assert ARCH.kv_bytes_per_token(SPEC, "window") == 3 * 4096
    assert ARCH.blocks_in_window(SPEC, 64) == 9
    assert ARCH.window_pool_bytes_per_slot(SPEC, 64) == 9 * 64 * 4096 * 3 \
        == 7_077_888
    dep = _cell()["deployment"]
    pools = (dep["kv_pool_tokens"] * 8192
             + dep["num_slots"] * ARCH.window_pool_bytes_per_slot(SPEC, 64))
    assert round(pools / 1e9, 2) == 5.74
    # weights and pools: what the fullest device holds before a step runs
    held = 2 * model_spec.num_params(SPEC) + pools
    assert round(held / 1e9, 1) == 13.5 and held > 0.25 * 16e9


def test_kernel_counts_by_the_kernels_instruction_names():
    assert model_spec.kernel_counts(
        SPEC, "paged_hybrid_decode_full", live_tokens=165_000,
        slots=128) == {"bytes": 165_000 * 4096 + 128 * 48 * 256 * 2}
    assert model_spec.kernel_counts(
        SPEC, "paged_hybrid_decode_window", live_tokens=50_000,
        slots=128) == {"bytes": 50_000 * 4096 + 128 * 64 * 256 * 2}
    # a window layer never reads more than its 9 blocks a slot
    assert model_spec.kernel_counts(
        SPEC, "paged_hybrid_decode_window", live_tokens=10 ** 9,
        slots=128) == {"bytes": 128 * 9 * 64 * 4096 + 128 * 64 * 256 * 2}
    want = 251.5 * 2048 * 512 * 2 + 1024 * (2048 + 512) * 2
    assert model_spec.kernel_counts(
        SPEC, "grouped_expert_matmul", experts_hit=251_500, pairs=1_024_000,
        layer_calls=1000, prefill_experts_hit=1, prefill_pairs=1,
        prefill_layer_calls=1) == {"bytes": want}
    assert model_spec.kernel_counts(
        SPEC, "grouped_expert_matmul_prefill", experts_hit=0, pairs=0,
        layer_calls=0, prefill_experts_hit=512, prefill_pairs=8192,
        prefill_layer_calls=2) == {
        "bytes": 256 * 2048 * 512 * 2 + 4096 * 2560 * 2}
    with pytest.raises(KeyError, match="no kernel named"):
        model_spec.kernel_counts(SPEC, "paged_mla_decode")
    for name, args in (("train_flops_per_token", (4096,)),
                       ("train_setup", (None, None)),
                       ("train_program_loss_and_grads", (None, None))):
        with pytest.raises(SystemExit, match="no train path"):
            getattr(ARCH, name)(SPEC, *args)


# ------------------------------------------------------- the configuration
def test_the_file_keeps_every_published_key_but_the_depth():
    pub = SPEC["published"]
    lists = ("layer_types", "mlp_layer_types",
             "num_attention_heads_per_layer")
    assert SPEC["reduced"] == ["num_hidden_layers"]
    for key, value in pub.items():
        if key == "num_hidden_layers":
            assert (SPEC[key], value) == (5, 40)
        else:
            # the three per-layer lists among them: a nested group is
            # copied whole, and what is run is its first five entries
            assert SPEC[key] == value, key
    assert all(len(pub[key]) == 40 for key in lists)
    run = ARCH.program_kwargs(SPEC)
    assert run["heads"] == (48, 64, 64, 64, 48)
    assert run["layer_kinds"] == (0, 1, 1, 1, 0)
    assert run["moe_layers"] == (0, 1, 1, 1, 1)
    widths = dict(hidden_size=2048, num_key_value_heads=8, head_dim=128,
                  sliding_window=512, intermediate_size=8192,
                  moe_intermediate_size=512,
                  shared_expert_intermediate_size=512, num_experts=256,
                  num_experts_per_tok=8, moe_routed_scaling_factor=2.5,
                  vocab_size=100352)
    assert {k: SPEC[k] for k in widths} == widths
    full = SPEC["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["beta_fast"],
            full["original_max_position_embeddings"],
            full["partial_rotary_factor"], full["rope_theta"]) \
        == ("yarn", 64, 64, 4096, 0.5, 500000)
    assert full["attention_factor"] == 1.4158883083359672 \
        == ARCH.yarn_table_factor(full)
    assert SPEC["rope_parameters"]["sliding_attention"] == dict(
        rope_type="default", rope_theta=10000, partial_rotary_factor=1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == SPEC["reduced"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert "8 pipeline stages of 5 layers" in SPEC["deployment"]
    assert "FIRST stage" in SPEC["deployment"]
    assert {"gating", "router_scoring", "gate_function"} <= set(
        SPEC["assumed"])
    cfg = ARCH.program_kwargs(SPEC)
    assert cfg["experts_held"] == (0, 256) and cfg["n_experts"] == 256
    assert cfg["heads"] == (48, 64, 64, 64, 48)
    assert cfg["layer_kinds"] == (0, 1, 1, 1, 0)
    assert cfg["moe_layers"] == (0, 1, 1, 1, 1)
    assert (cfg["rotary_dim"], cfg["swa_rotary_dim"]) == (64, 128)
    assert cfg["yarn"] == dict(factor=64.0, original_max_seq=4096,
                               beta_fast=64.0, beta_slow=1.0, mscale=1.0,
                               mscale_all_dim=0.0)
    # the cell and the lists it joins
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == dict(cells[CELL], config=NAME, chips=1,
                               traffic="agent-mixed-decode")
    lists = {m["name"]: m.get("workloads", [])
             for m in bench["end_to_end"] + bench["per_layer"]}
    for name in ("output_tokens_per_s", "replica_ready_s",
                 "expert_pairs_dropped", "window_pool_live_pct",
                 "experts_hit_pct", "grouped_expert_matmul_roofline",
                 "paged_hybrid_decode_full_roofline",
                 "paged_hybrid_decode_window_roofline"):
        assert CELL in lists[name], name


def test_a_configuration_that_is_not_this_block_exits_by_name():
    with pytest.raises(SystemExit, match="needs the keys"):
        ARCH.check_config({k: v for k, v in SPEC.items() if k != "gating"})
    with pytest.raises(SystemExit, match="a gate on every head"):
        ARCH.check_config(dict(SPEC, gating=False))
    with pytest.raises(SystemExit, match="not 33.44 B"):
        ARCH.check_config(dict(SPEC, published=dict(UNCUT, num_experts=128)))


def test_the_weights_tree_holds_the_cuts_parameters():
    leaves = jax.tree.leaves(ARCH.weight_shapes(SPEC),
                             is_leaf=lambda t: isinstance(t, tuple))
    assert sum(math.prod(s) for s in leaves) == model_spec.num_params(SPEC)


# ------------------------------------------------------------- the traffic
def test_agent_mixed_decode_sends_the_same_lengths_for_every_seed():
    mix = traffic_gen.load_mix("agent-mixed-decode")
    assert mix["kind"] == "closed_loop_handle" and mix["clients"] == 256
    dep = _cell()["deployment"]
    assert mix["clients"] == 2 * dep["num_slots"]
    shapes = []
    for seed in (1, 2_147_483_999, 3_000_000_000):
        stream = traffic_gen.request_stream(mix, seed, SPEC["vocab_size"])
        reqs = [next(stream) for _ in range(512)]
        assert all(0 <= t < 100352 for r in reqs for t in r["prompt"])
        shapes.append([(len(r["prompt"]), r["max_tokens"]) for r in reqs])
    assert shapes[0] == shapes[1] == shapes[2]
    plens = [p for p, _ in shapes[0][:256]]
    olens = [o for _, o in shapes[0][:256]]
    assert min(plens) == 160 and max(plens) == 2048
    assert min(olens) == 128 and max(olens) == 2560
    assert round(sum(plens) / 256) == 699 and round(sum(olens) / 256) == 797
    assert sum(p > 1024 for p in plens) == 56            # 22%
    assert sum(p == 2048 for p in plens) == 16           # 6% at the cap
    assert max(p + o for p, o in shapes[0]) <= dep["max_seq"] == 4608
    # some requests never leave the window of 512, the longest pass it
    # nine times
    inside = sum(p + o <= 512 for p, o in shapes[0][:256])
    assert 0 < inside < 64
    assert traffic_gen.prompt_buckets(mix) == [256, 512, 1024, 2048]
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
    """The decode step at 128 slots and the 2048 prefill bucket: the
    hybrid kernel once a layer under both names (a window layer's 9
    blocks in one step) and the expert products are there, both pools
    are updated in place (the temporaries are a small part of them), and
    the sum fits."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dep = _cell()["deployment"]
    decode, bucket = sizing.serve_programs(SPEC, dep, device)
    compiled = decode.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 5 + 3 * 4
    assert "paged_hybrid_decode_full" in text
    assert "paged_hybrid_decode_window" in text
    assert "grouped_expert_matmul" in text
    mem = compiled.memory_analysis()
    assert sizing.total_bytes(mem) < sizing.HBM_BYTES
    full = (1 + dep["kv_pool_tokens"] // 64) * 64 * 8192
    window = (1 + dep["num_slots"] * 9) * 64 * 3 * 4096
    assert mem.argument_size_in_bytes >= \
        2 * model_spec.num_params(SPEC) + full + window
    assert mem.alias_size_in_bytes >= full + window
    assert mem.temp_size_in_bytes < window / 4          # no pool-shaped copy
    pre = bucket(2048).compile()
    assert "grouped_expert_matmul_prefill" in pre.as_text()
    mem = pre.memory_analysis()
    assert sizing.total_bytes(mem) < sizing.HBM_BYTES
    assert mem.alias_size_in_bytes >= full + window
    assert mem.temp_size_in_bytes < full / 3


# ------------------------------------------------------- experts_hit_pct
def test_experts_hit_pct_reads_the_hit_share_of_the_routers_width():
    from benchmark import run as bench_run

    reader = bench_run.load_reader("experts_hit_pct")

    def stats(calls, hit):
        return {"stats": {"model_counters": {
            "expert_layer_calls": calls, "experts_hit": hit}}, "now": calls}

    run = {"spec": SPEC, "raw": {"open": stats(400, 100_000),
                                 "close": stats(1400, 351_000)}}
    assert reader(run) == pytest.approx(100 * 251_000 / (1000 * 256))
    assert reader(dict(run, raw={"open": stats(4, 9),
                                 "close": stats(4, 9)})) is None
    assert reader(dict(run, raw={})) is None             # no serve cell
    assert reader(dict(run, raw={"open": {"stats": {}, "now": 0},
                                 "close": {"stats": {}, "now": 1}})) is None
    # a block whose adapter states no router width has nothing to read
    dense = model_spec.load_config("mistral-7b-l16")
    assert reader(dict(run, spec=dense)) is None


# ------------------------------------------------------------- a rehearsal
TINY = dict(
    SPEC, name="tiny-laguna", source="test",
    limits="benchmark/limits/tiny-laguna.json",
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_key_value_heads=2, head_dim=16, num_attention_heads=6,
    num_attention_heads_per_layer=[6, 8, 8, 8, 6], num_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, sliding_window=96,
    max_position_embeddings=1024, reduced=[],
    rope_parameters=dict(SPEC["rope_parameters"], full_attention=dict(
        SPEC["rope_parameters"]["full_attention"], factor=4,
        original_max_position_embeddings=32, beta_fast=4,
        attention_factor=0.1 * math.log(4) + 1)))
TINY.pop("published")
WHOLE_METRICS = ("experts_hit_pct", "expert_pairs_per_step",
                 "expert_load_max_over_mean", "expert_pairs_dropped",
                 "window_pool_live_pct", "engine_step_ms",
                 "slot_occupancy_pct")


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

    put("configs/tiny-laguna.json", TINY)
    put("limits/tiny-laguna.json", {"limits": {
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
        "name": "tiny-laguna", "source": "test",
        "file": "benchmark/configs/tiny-laguna.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-cell", "config": "tiny-laguna", "traffic": "tiny-mix",
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
    assert set(WHOLE_METRICS) <= set(got), sorted(got)
    assert got["expert_pairs_dropped"]["value"] == 0
    # every expert is held: 4 pairs for each running slot, at most 3 slots
    assert 0 < got["expert_pairs_per_step"]["value"] <= 12
    assert 0 < got["experts_hit_pct"]["value"] <= 100 * 12 / 16
    assert 0 < got["window_pool_live_pct"]["value"] <= 100
    assert "paged_hybrid_decode_full_roofline" not in got  # no kernel
    assert "read router_choices_flipped_by_bf16_activations" in proc.stdout
