"""What PR 48 added to the benchmark as new files: the ``sdar`` adapter's
counts against hand counts, the configuration's file against its
published keys, the ``blockdiff-batch-decode`` mix, the cell's programs
compiled for a described v5e, the block turn's counter reader, and a
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

from benchmark import fold, model_spec, sizing, traffic_gen
from benchmark import run as bench_run

BENCH = model_spec.HERE
ROOT = os.path.dirname(BENCH)
NAME = "sdar-30b-a3b-l7"
CELL = "serve-blockdiff-moe-decode"
SPEC = model_spec.load_config(NAME)
ARCH = model_spec.adapter(SPEC)
UNCUT = dict(SPEC["published"], architecture="sdar")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)


def _cell(name=CELL):
    with open(os.path.join(BENCH, "cells", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ counts
def test_parameters_of_the_uncut_model_and_of_the_cut():
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert ARCH.attention_params(SPEC) == attention == 18_874_368
    assert ARCH.expert_params(SPEC) == 3 * 2048 * 768 == 4_718_592
    layer = attention + 2048 * 128 + 128 * 4_718_592 + 2 * (2048 + 128)
    assert layer == 623_120_640
    cut = 7 * layer + 2 * 151_936 * 2048 + 2048
    assert model_spec.num_params(SPEC) == cut
    assert round(cut / 1e9, 2) == 4.98
    assert round(2 * cut / 1024 ** 3, 2) == 9.28        # GiB in bfloat16
    uncut = model_spec.num_params(UNCUT)
    assert uncut == 48 * layer + 2 * 151_936 * 2048 + 2048
    assert abs(uncut / 30.5e9 - 1) < 0.01               # "30B-A3B"
    assert round(ARCH.active_params(UNCUT) / 1e9, 2) == 3.35
    mp = model_spec.matrix_params(SPEC)
    assert mp == {"attention": 7 * attention,
                  "experts": 7 * 128 * 4_718_592, "router": 7 * 2048 * 128,
                  "head": 2048 * 151_936}
    assert model_spec.kv_bytes_per_token(SPEC) == 4 * 2 * 128 * 2 == 2048


def test_the_weights_tree_holds_the_cuts_parameters():
    leaves = jax.tree.leaves(ARCH.weight_shapes(SPEC),
                             is_leaf=lambda t: isinstance(t, tuple))
    assert sum(math.prod(s) for s in leaves) == model_spec.num_params(SPEC)
    std, stds = ARCH.weight_stds(SPEC)
    assert std == 2048 ** -0.5
    assert stds["q_norm"] == stds["k_norm"] == 0.1
    assert stds["we_down"] == stds["wo"] == std / 14 ** 0.5
    assert stds["router"] == 2 * std        # logits of std 2: PERF.md, PR 48


def test_kernel_counts_by_the_kernels_instruction_names():
    # a block step's attention reads every live row once (the block's
    # own among them) and 4 rows of every query head a slot in and out
    assert model_spec.kernel_counts(
        SPEC, "paged_decode_attention", live_tokens=130_000, slots=128,
        block_length=4) == {
        "bytes": 130_000 * 2048 + 128 * 2 * 4 * 32 * 128 * 2}
    want = 128 * 2048 * 768 * 2 + 4096 * (2048 + 768) * 2
    assert model_spec.kernel_counts(
        SPEC, "grouped_expert_matmul", experts_hit=128_000,
        pairs=4_096_000, layer_calls=1000, prefill_experts_hit=1,
        prefill_pairs=1, prefill_layer_calls=1) == {"bytes": want}
    assert model_spec.kernel_counts(
        SPEC, "grouped_expert_matmul_prefill", experts_hit=0, pairs=0,
        layer_calls=0, prefill_experts_hit=256, prefill_pairs=4096,
        prefill_layer_calls=2) == {
        "bytes": 128 * 2048 * 768 * 2 + 2048 * 2816 * 2}
    with pytest.raises(KeyError, match="no kernel named"):
        model_spec.kernel_counts(SPEC, "paged_mla_decode")
    for name, args in (("train_flops_per_token", (4096,)),
                       ("train_setup", (None, None)),
                       ("train_program_loss_and_grads", (None, None))):
        with pytest.raises(SystemExit, match="no train path"):
            getattr(ARCH, name)(SPEC, *args)
    with pytest.raises(SystemExit, match="no backward pass"):
        model_spec.reference(SPEC).last_block_loss_and_grads(None, None,
                                                             SPEC)


# ------------------------------------------------------- the configuration
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def test_the_file_keeps_every_published_key_but_the_depth():
    assert SPEC["reduced"] == ["num_hidden_layers"]
    for key, value in CATALOG.items():
        assert SPEC["published"][key] == value, key
        if key == "num_hidden_layers":
            assert SPEC[key] == 7
        else:
            assert SPEC[key] == value, key
    for key, value in SPEC["published"].items():
        if key != "num_hidden_layers":
            assert SPEC[key] == value, key
    assert {"block_length", "mask_token_id", "denoising_steps", "remasking",
            "qk_norm", "no_shift", "torch_dtype"} <= set(SPEC["assumed"])
    assert (SPEC["block_length"], SPEC["mask_token_id"],
            SPEC["denoising_steps"], SPEC["remasking"]) == (
        4, 151669, 2, "low_confidence_static")
    assert "seven pipeline stages of 7, 7, 7, 7, 7, 7 and 6" \
        in SPEC["deployment"]
    assert "1 chip shares a layer" in SPEC["deployment"]
    entry = [c for c in BENCHMARK["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == SPEC["reduced"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == SPEC["source"] and len(entry["source"]) <= 200
    assert "JetLM/SDAR-30B-A3B-Chat" in entry["source"]
    assert "sdar_moe" in entry["source"]
    dep = _cell()["deployment"]
    kw = ARCH.program_kwargs(SPEC, dep)
    assert kw == dict(
        vocab_size=151936, hidden=2048, n_layers=7, n_heads=32,
        n_kv_heads=4, head_dim=128, rope_theta=1e6, expert_dim=768,
        n_experts=128, top_k=8, experts_held=(0, 128), norm_eps=1e-6,
        max_seq=32768, block_length=4, denoising_steps=2,
        mask_token_id=151669, remasking="low_confidence_static")
    # the cell's steps a block reach the program through the deployment
    assert ARCH.program_kwargs(SPEC, dict(dep, denoising_steps=4))[
        "denoising_steps"] == 4
    assert "denoising_steps" not in ARCH.engine_kwargs.__code__.co_varnames


# what no other configuration has: the block step's own readings
OWN = ("block_step_dev_ms", "block_step_attention_dev_ms",
       "block_step_attention_roofline", "block_step_qkv_store_dev_ms",
       "block_step_experts_dev_ms", "block_step_head_dev_ms",
       "block_step_decide_dev_ms", "block_step_unnamed_dev_ms",
       "tokens_per_block_step", "commit_step_share_pct",
       "expert_layer_dev_ms.bd")
# the readings of the engine, the routed layer and the prefill that the
# cell shares with other cells, by their names up to a tag: the cell's PR
# brought `<name>.bd`, a fold takes a tag off, and neither is this
# test's business
SHARED = ("replica_ready_s", "engine_step_ms", "device_idle_pct",
          "overlapped_turn_pct", "grouped_expert_matmul_roofline",
          "expert_pairs_per_step", "expert_load_max_over_mean",
          "expert_pairs_dropped", "experts_hit_pct", "prefill_dev_share_pct",
          "prefill_flash_dev_ms", "prefill_experts_dev_ms",
          "prefill_expert_products_dev_ms", "prefill_unnamed_dev_ms",
          "turn_decode_wait_ms", "turn_prefill_wait_ms", "turn_host_ms",
          "prompts_per_admitting_turn", "gap_after_prefill_ms",
          "gap_in_turn_ms", "other_programs_dev_ms")


def _mine():
    """The per-layer entries that list the cell."""
    return [m for m in BENCHMARK["per_layer"]
            if CELL in m.get("workloads", ())]


def test_the_cell_and_the_lists_it_joins():
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    assert cells[CELL] == dict(cells[CELL], config=NAME, chips=1,
                               traffic="blockdiff-batch-decode")
    assert len(cells[CELL]["why"]) <= 200
    lists = {m["name"]: m.get("workloads")
             for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]}
    assert CELL in lists["output_tokens_per_s"]
    # the two entries that move setup_s and list no cells are reported
    # here as everywhere (their readers find the run's own numbers)
    for name in ("compiles_in_window", "peak_hbm_gb"):
        assert lists[name] is None
    mine = [m["name"] for m in _mine()]
    # the engine runs no decode step here: a block step, and no slot of
    # it is ever half full
    assert not [n for n in mine if n.startswith(("decode_",
                                                 "slot_occupancy"))]
    # no program answers to another's name, no part to another block's
    parts = {"unnamed"}      # what no part of any block names
    for name in ("base", "sdar"):
        with open(os.path.join(BENCH, "layer_metrics", "parts",
                               name + ".json")) as f:
            parts.update(json.load(f)["parts"])
    for name in mine:
        reader, args = fold.resolved(ROOT, name)
        assert args.get("program", "") in (
            "", "^jit_block_step", "^jit_block_decide", "^jit_prefill"), name
        if reader == "_dev_ms_by_part":
            assert set(args.get("parts", ())) <= parts, name


@pytest.mark.parametrize("name", OWN)
def test_the_cell_reports_an_entry_of_its_own_mechanism(name):
    assert [m for m in _mine() if m["name"] == name], name
    assert callable(bench_run.load_reader(name))


@pytest.mark.parametrize("base", SHARED)
def test_the_cell_is_in_the_list_of_a_shared_entry(base):
    """Under whatever tag, once: one entry of that reading lists it
    (``expert_layer_dev_ms.bd`` reads the block step and is the cell's
    own)."""
    assert len([m for m in _mine()
                if m["name"].split(".")[0] == base]) == 1, base


def test_a_configuration_that_is_not_this_block_exits_by_name():
    with pytest.raises(SystemExit, match="needs the keys"):
        ARCH.check_config({k: v for k, v in SPEC.items()
                           if k != "block_length"})
    with pytest.raises(SystemExit, match="low_confidence_static"):
        ARCH.check_config(dict(SPEC, remasking="low_confidence_dynamic"))
    with pytest.raises(SystemExit, match="not 30.5 B"):
        ARCH.check_config(dict(SPEC, published=dict(UNCUT, num_experts=64)))


def test_the_limits_fail_the_control_and_pass_the_program():
    lim = model_spec.limits(SPEC)
    # the third is judged by the adapter itself, on the denoising steps
    assert set(lim) == {"serve_prefill_logits_rel_err",
                        "serve_decode_logits_rel_err",
                        "serve_denoise_logits_rel_err"}
    for entry in lim.values():
        assert entry["program_largest"] < entry["limit"]
        assert entry["seeds"] >= 12 and entry["control_seeds"] >= 12
        # every limit, on every seed of the control
        assert entry["limit"] < entry["control_int8_smallest"]


# ------------------------------------------------------------- the traffic
def test_blockdiff_batch_decode_sends_the_same_lengths_for_every_seed():
    mix = traffic_gen.load_mix("blockdiff-batch-decode")
    assert mix["kind"] == "closed_loop_handle" and mix["clients"] == 256
    dep = _cell()["deployment"]
    assert mix["clients"] == 2 * dep["num_slots"]
    assert (mix["block_length"], mix["denoising_steps"]) == (
        SPEC["block_length"], dep["denoising_steps"])
    shapes = []
    for seed in (1, 2_147_483_999, 3_000_000_000):
        stream = traffic_gen.request_stream(mix, seed, SPEC["vocab_size"])
        reqs = [next(stream) for _ in range(512)]
        assert all(0 <= t < 151936 for r in reqs for t in r["prompt"])
        shapes.append([(len(r["prompt"]), r["max_tokens"]) for r in reqs])
    assert shapes[0] == shapes[1] == shapes[2]
    plens = sorted(p for p, _ in shapes[0][:256])
    olens = [o for _, o in shapes[0][:256]]
    assert plens[0] == 64 and plens[-1] == 1024
    assert 240 <= plens[128] <= 270                      # median 256
    assert min(olens) >= 256 and max(olens) <= 1024
    assert round(sum(olens) / 256, 1) == 639.5
    # answers of any length, not only whole blocks
    assert {o % 4 for o in olens} == {0, 1, 2, 3}
    assert {p % 4 for p in plens} == {0, 1, 2, 3}
    assert max(p + o for p, o in shapes[0]) <= dep["max_seq"] == 2048
    assert traffic_gen.prompt_buckets(mix) == [64, 128, 256, 512, 1024]
    assert mix["lead_s"] >= 36.0        # three generations of 12 s answers


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
    """The block step at 128 slots (512 rows), the deciding program and
    the smallest and largest prefill buckets: the paged decode kernel
    once a layer and three expert products a layer are there, the pool
    is updated in place by every program (no pool-sized temporary: a
    prefill that wrote whole blocks had four), each weight is read
    once, and the sums fit."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dep = _cell()["deployment"]
    step, bucket = sizing.serve_programs(SPEC, dep, device)
    compiled = step.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 7 + 3 * 7
    assert "paged_decode_attention" in text
    assert "grouped_expert_matmul" in text
    assert not [line for line in text.splitlines()
                if ".remat" in line and "params__" in line]
    mem = compiled.memory_analysis()
    assert sizing.total_bytes(mem) < sizing.HBM_BYTES
    pool = 2 * 7 * (1 + dep["kv_pool_tokens"] // 64) * 64 * 2048 // 2
    assert pool == 7 * 3073 * 64 * 2048
    assert mem.argument_size_in_bytes >= \
        2 * model_spec.num_params(SPEC) + pool
    assert mem.alias_size_in_bytes >= pool
    logits = dep["num_slots"] * 4 * SPEC["vocab_size"] * 4
    assert logits <= mem.temp_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes < 2.2 * logits
    assert sizing.total_bytes(mem) > 0.75 * sizing.HBM_BYTES   # 12.5 GiB
    decide = ARCH.lower_decide(SPEC, dep, device).compile()
    assert sizing.total_bytes(decide.memory_analysis()) < 1.1 * logits
    for pad_len in (64, 1024):
        pre = bucket(pad_len).compile()
        assert "grouped_expert_matmul_prefill" in pre.as_text()
        assert "flash_attention_fwd" in pre.as_text()
        mem = pre.memory_analysis()
        assert sizing.total_bytes(mem) < sizing.HBM_BYTES
        assert mem.alias_size_in_bytes >= pool
        assert mem.temp_size_in_bytes < pool / 8        # no copy of the pool


# ------------------------------------------------------ the block counters
def test_the_block_counters_reader():
    tokens = bench_run.load_reader("tokens_per_block_step")
    share = bench_run.load_reader("commit_step_share_pct")

    def stats(steps, slot_steps, commits, tokens):
        return {"stats": {"block_steps": steps, "slot_steps": slot_steps,
                          "commit_steps": commits,
                          "tokens_generated": tokens}, "now": steps}

    run = {"cellfile": {"deployment": {"num_slots": 128}},
           "raw": {"open": stats(100, 12_000, 4_000, 16_000),
                   "close": stats(3100, 396_000, 132_000, 528_000)}}
    assert tokens(run) == pytest.approx(512_000 / (3000 * 128))    # 4/3
    assert share(run) == pytest.approx(100 * 128_000 / 384_000)
    same = dict(run, raw={"open": stats(5, 5, 1, 4),
                          "close": stats(5, 5, 1, 4)})
    assert tokens(same) is None and share(same) is None
    assert tokens(dict(run, raw={})) is None             # no serve cell
    # a program without the counters (the parent's) has nothing to read
    bare = {"stats": {"steps": 3, "tokens_generated": 3}, "now": 0}
    assert tokens(dict(run, raw={"open": bare, "close": bare})) is None
    assert share(dict(run, raw={"open": bare, "close": bare})) is None


# ------------------------------------------------------------- a rehearsal
TINY = dict(
    SPEC, name="tiny-sdar", source="test",
    limits="benchmark/limits/tiny-sdar.json",
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    max_position_embeddings=1024, mask_token_id=250, reduced=[])
TINY.pop("published")
BLOCK_METRICS = ("tokens_per_block_step", "commit_step_share_pct",
                 "engine_step_ms", "overlapped_turn_pct",
                 "expert_pairs_per_step", "expert_load_max_over_mean",
                 "expert_pairs_dropped", "experts_hit_pct",
                 "replica_ready_s", "compiles_in_window")


def test_a_tiny_configuration_of_the_block_runs_through_the_harness(
        tmp_path):
    """On the CPU (pretend chip, nothing it prints is a measurement):
    the adapter, the reference, the check, the warm-up's answers of 3
    tokens, the probe and the cell's counter metrics work end to end
    through ``run.py`` and the engine's block turn; the readers of
    kernels find no kernel here and leave their metrics out without
    raising."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "ray_tpu"), tmp_path / "ray_tpu")
    bench = json.loads(json.dumps(BENCHMARK))

    def put(rel, obj):
        (tmp_path / "benchmark" / rel).write_text(json.dumps(obj))

    put("configs/tiny-sdar.json", TINY)
    put("limits/tiny-sdar.json", {"limits": {
        "serve_prefill_logits_rel_err": {"limit": 0.15},
        "serve_decode_logits_rel_err": {"limit": 0.15}}})
    put("cells/tiny-cell.json", {"deployment": {
        "num_slots": 3, "max_seq": 512, "kv_block_size": 64,
        "kv_pool_tokens": 1536, "denoising_steps": 2,
        "max_ongoing_requests": 16}})
    put("traffic/tiny-mix.json", {
        "kind": "closed_loop_handle", "clients": 6, "block": 16,
        "prompt_len": {"dist": "uniform", "min": 40, "max": 100},
        "output_len": {"dist": "uniform", "min": 5, "max": 11},
        "temperature": 0.0, "lead_s": 1.0, "drain_s": 30.0,
        "trace_offset_s": 0.5, "trace_s": 1.0})
    bench["configs"].append({
        "name": "tiny-sdar", "source": "test",
        "file": "benchmark/configs/tiny-sdar.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-cell", "config": "tiny-sdar", "traffic": "tiny-mix",
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
    assert line["compared"]["answers_of_wrong_shape"]["value"] == 0
    assert line["compared"]["greedy_probe_differs"]["value"] == 0
    got = line["metrics"]
    assert set(BLOCK_METRICS) <= set(got), sorted(got)
    assert got["expert_pairs_dropped"]["value"] == 0
    # every expert is held: 2 pairs a position, 4 positions a running slot
    assert 0 < got["expert_pairs_per_step"]["value"] <= 3 * 4 * 2
    assert 0 < got["tokens_per_block_step"]["value"] <= 4 / 3
    assert 25 < got["commit_step_share_pct"]["value"] < 50
    assert "block_step_attention_roofline" not in got    # no kernel
    assert "slot_occupancy_pct" not in got
    assert "read router_choices_flipped_by_bf16_activations" in proc.stdout
    # the check ran on the replica's engine: its denoising steps too
    assert "read serve_denoise_logits_rel_err" in proc.stdout
