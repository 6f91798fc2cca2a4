"""What PR 39 added to the benchmark as new files: the ``nemotron_h``
adapter's counts against hand counts, the configuration's file against
its published keys, the ``chat-turnover`` mix, the cell's programs
compiled for a described v5e, the readers of the recurrent state and of
the state-space parts, the check on an engine handed over, a checkout
without the program's module, and a rehearsal of a tiny configuration of
the block through ``run.py`` with the cell's per-layer metrics."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from benchmark import model_spec, sizing, traffic_gen
from benchmark import run as bench_run

BENCH = model_spec.HERE
ROOT = os.path.dirname(BENCH)
NAME = "nemotron3-super-ep4-l11"
CELL = "serve-ssm-latent-moe-chat"
SPEC = model_spec.load_config(NAME)
ARCH = model_spec.adapter(SPEC)
UNCUT = dict(SPEC["published"], architecture="nemotron_h")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)


def _cell(name=CELL):
    with open(os.path.join(BENCH, "cells", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ counts
def test_parameters_of_the_uncut_model_and_of_the_cut():
    """By hand, from the published keys: 40 Mamba-2 layers of 109.64 M,
    8 attention layers of 35.66 M, 40 expert layers of 54.53 M beside
    512 experts of 5.505 M, 1.07 B in the embedding and the head: 120.7
    B, 12.8 B a token ("120B-A12B"). The cut: 5, 1 and 5 of them with
    128 experts held and a quarter of the vocabulary, 4.65 B."""
    each = ARCH.mixer_params(UNCUT)
    assert each["M"] == 4096 + 4096 * 18560 + 5 * 10240 + 3 * 128 + 8192 \
        + 8192 * 4096 == 109_640_064
    assert each["*"] == 4096 + 2 * 4096 * 4096 + 2 * 4096 * 256 == 35_655_680
    assert ARCH.expert_params(UNCUT) == 2 * 1024 * 2688 == 5_505_024
    assert each["E"] - 512 * 5_505_024 == 4096 + 4096 * 512 + 512 \
        + 2 * 4096 * 1024 + 2 * 4096 * 5376 == 54_530_560
    p = UNCUT["hybrid_override_pattern"]
    assert (p.count("M"), p.count("E"), p.count("*")) == (40, 40, 8)
    assert model_spec.num_params(UNCUT) == 40 * each["M"] + 8 * each["*"] \
        + 40 * each["E"] + 2 * 131072 * 4096 + 4096 == 120_668_707_840
    assert round(ARCH.active_params(UNCUT) / 1e9, 2) == 12.77
    assert ARCH.pattern(SPEC) == "MEMEMEM*EME"
    cut = ARCH.mixer_params(SPEC)
    assert cut["E"] == 54_530_560 + 128 * 5_505_024 == 759_173_632
    assert model_spec.num_params(SPEC) == 5 * cut["M"] + cut["*"] \
        + 5 * cut["E"] + 2 * 32768 * 4096 + 4096 == 4_648_163_712
    assert sum(model_spec.matrix_params(SPEC).values()) == \
        model_spec.num_params(SPEC) - 32768 * 4096 - 4096 \
        - 5 * (4096 + 8192 + 5 * 10240 + 3 * 128) - 4096 - 5 * (4096 + 512)
    # a token passes through what every chip holds plus 22 experts
    assert ARCH.active_params(dict(SPEC, router_width=512)) == \
        model_spec.num_params(dict(SPEC, n_routed_experts=512)) \
        - 5 * 490 * 5_505_024


def test_state_a_slot_and_a_cached_token():
    """A sequence's recurrent state is 128 heads x 64 x 128 float32 and
    3 columns of the 10,240-wide convolution in bf16 a state-space
    layer, however long it is: 21.3 MB over the five, as many bytes as
    20,780 tokens of this model's KV (1 KB a token in its one attention
    layer): the state, not the KV pool, sets the slot count."""
    assert model_spec.kv_bytes_per_token(SPEC) == 2 * 2 * 128 * 2 == 1024
    assert ARCH.state_bytes_per_slot(SPEC) == \
        5 * (4 * 128 * 64 * 128 + 2 * 3 * 10240) == 21_278_720
    dep = _cell()["deployment"]
    state = dep["num_slots"] * ARCH.state_bytes_per_slot(SPEC)
    kv = dep["kv_pool_tokens"] * 1024
    assert round(state / 1e9, 2) == 4.09 and round(kv / 1e9, 2) == 0.40
    cfg_bytes = ARCH.program_kwargs(SPEC)
    assert cfg_bytes["pattern"] == "MEMEMEM*EME"
    assert cfg_bytes["experts_held"] == (0, 128)
    assert cfg_bytes["n_experts"] == 512 and cfg_bytes["top_k"] == 22


def test_kernel_counts_by_the_kernels_instruction_names():
    """One call of the state update at 192 running slots, by hand: a
    slot's state read and written (2 x 4 x 8192 x 128 = 8,388,608 B),
    ``dt x`` in and ``y`` out (2 x 4 x 8192), a decay a head (4 x 128)
    and B and C (2 x 4 x 8 x 128): 8,462,848 B a slot, 1.625 GB a
    layer."""
    got = model_spec.kernel_counts(SPEC, "ssm_decode_update",
                                   slots_live=5 * 192 * 100,
                                   layer_calls=5 * 100)
    slot = 8_388_608 + 65_536 + 512 + 8_192
    assert slot == 8_462_848 and got == {"bytes": 192.0 * slot}
    # half as many slots running, half the bytes; no call, no division
    assert model_spec.kernel_counts(
        SPEC, "ssm_decode_update", slots_live=96 * 5.0,
        layer_calls=5.0)["bytes"] == 96 * slot
    assert model_spec.kernel_counts(SPEC, "ssm_decode_update", slots_live=0,
                                    layer_calls=0)["bytes"] == 0
    # a grouped product: each expert hit reads its 1024 x 2688 matrix
    # (either of the two: the same count), each pair a row in and out
    mm = model_spec.kernel_counts(
        SPEC, "grouped_expert_matmul", experts_hit=128 * 10, pairs=1056 * 10,
        layer_calls=10)["bytes"]
    assert mm == 128 * 1024 * 2688 * 2 + 1056 * (1024 + 2688) * 2
    pre = model_spec.kernel_counts(
        SPEC, "grouped_expert_matmul_prefill", prefill_experts_hit=100,
        prefill_pairs=1000, prefill_layer_calls=1)["bytes"]
    assert pre == 100 * 1024 * 2688 * 2 + 1000 * (1024 + 2688) * 2
    paged = model_spec.kernel_counts(SPEC, "paged_decode_attention",
                                     live_tokens=100_000, slots=192)["bytes"]
    assert paged == 100_000 * 1024 + 2 * 192 * 32 * 128 * 2
    with pytest.raises(KeyError, match="no kernel named"):
        model_spec.kernel_counts(SPEC, "flash_attention_fwd", batch=1, seq=1)


# ---------------------------------------------------------------- the file
def test_the_file_keeps_every_published_key_but_the_three_cuts():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows
               if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == NAME)
    assert entry["source"].startswith(row["source_url"])
    assert "model_type nemotron_h" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert SPEC["published"] == row["config"]
    differs = sorted(k for k, v in row["config"].items() if SPEC.get(k) != v)
    assert differs == sorted(entry["reduced"]) == sorted(SPEC["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (SPEC["num_hidden_layers"], SPEC["n_routed_experts"],
            SPEC["vocab_size"]) == (11, 128, 32768)
    assert SPEC["router_width"] == 512 and SPEC["experts_first"] == 0
    # the floors: a whole period of the pattern, 8 experts, an eighth of
    # the vocabulary; no width is among the cuts
    assert SPEC["n_routed_experts"] >= 8
    assert SPEC["vocab_size"] * 8 >= row["config"]["vocab_size"]
    for key in ("reduced_why", "equations", "not_run", "assumed",
                "deployment"):
        assert SPEC[key], key
    assert {"attention_position", "gated_norm", "state_dtype",
            "router_dtype", "time_step", "recurrence_weights",
            "norm_storage"} <= set(SPEC["assumed"])
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": NAME, "traffic": "chat-turnover",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200


def test_a_configuration_that_is_not_this_block_exits_by_name():
    with pytest.raises(SystemExit, match="needs the keys"):
        ARCH.check_config({"name": "x", "architecture": "nemotron_h"})
    with pytest.raises(SystemExit, match="relu2 experts without a gate"):
        ARCH.check_config(dict(SPEC, mlp_hidden_act="silu"))
    with pytest.raises(SystemExit, match="not 120.67 B"):
        ARCH.check_config(dict(SPEC, published=dict(
            SPEC["published"], n_routed_experts=256)))


def test_a_checkout_without_the_programs_module_exits_at_once(tmp_path):
    """The benchmark's files laid over a checkout from before this PR
    (what the driver does to the parent): the new cell's run ends in the
    driver, before any process is started, naming the missing file; it
    cannot hang."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.makedirs(tmp_path / "ray_tpu" / "models")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483999", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode not in (0, None)
    assert "cannot run the nemotron_h block: no file" in proc.stderr
    assert "ray_tpu/models/nemotron_h.py" in proc.stderr


def test_the_weights_tree_holds_the_cuts_parameters():
    shapes = jax.tree.leaves(ARCH.weight_shapes(SPEC),
                             is_leaf=lambda t: isinstance(t, tuple))
    n = 0
    for shape in shapes:
        size = 1
        for d in shape:
            size *= d
        n += size
    assert n == model_spec.num_params(SPEC)


# ------------------------------------------------------------- the traffic
def test_chat_turnover_sends_the_same_lengths_for_every_seed():
    mix = traffic_gen.load_mix("chat-turnover")
    dep = _cell()["deployment"]
    assert mix["kind"] == "closed_loop_handle"
    assert mix["clients"] == mix["block"] == 2 * dep["num_slots"] == 384
    shapes = []
    for seed in (1, 2_147_483_999, 3_000_000_000):
        stream = traffic_gen.request_stream(mix, seed, SPEC["vocab_size"])
        reqs = [next(stream) for _ in range(768)]
        assert all(0 <= t < 32768 for r in reqs for t in r["prompt"])
        shapes.append([(len(r["prompt"]), r["max_tokens"]) for r in reqs])
    assert shapes[0] == shapes[1] == shapes[2]
    plens = [p for p, _ in shapes[0][:384]]
    olens = [o for _, o in shapes[0][:384]]
    assert min(plens) == 32 and max(plens) == 1024
    assert min(olens) == 64 and max(olens) == 1024
    assert sorted(plens)[192] in range(155, 166)         # median 160
    assert sorted(olens)[192] in range(314, 327)         # median 320
    assert round(sum(plens) / 384) == 217 and round(sum(olens) / 384) == 375
    assert max(p + o for p, o in shapes[0]) <= dep["max_seq"] == 2048
    # the five buckets a run warms, and nothing is preempted
    assert traffic_gen.prompt_buckets(mix) == [64, 128, 256, 512, 1024]
    assert dep["kv_pool_tokens"] == dep["num_slots"] * dep["max_seq"]
    assert dep["kv_block_size"] == 64


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
    """The decode step at the cell's slots and its five prefill buckets:
    the state update once a state-space layer, two grouped products an
    expert layer and the paged kernel once are there; the recurrent
    state, the convolution columns and the KV pool are updated in place
    (the temporaries are a small part of them: no state-shaped copy, in
    the prefill's write of one slot's row least of all), and the sum
    fits."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dep = _cell()["deployment"]
    decode, bucket = sizing.serve_programs(SPEC, dep, device)
    compiled = decode.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 5 + 2 * 5 + 1
    for name in ("ssm_decode_update", "grouped_expert_matmul",
                 "paged_decode_attention"):
        assert name in text
    mem = compiled.memory_analysis()
    assert sizing.total_bytes(mem) < sizing.HBM_BYTES
    slots = dep["num_slots"]
    state = slots * 5 * 4 * 8192 * 128
    cache = (slots * ARCH.state_bytes_per_slot(SPEC)
             + (1 + dep["kv_pool_tokens"] // 64) * 64 * 1024)
    assert mem.argument_size_in_bytes >= \
        2 * model_spec.num_params(SPEC) + cache
    assert mem.alias_size_in_bytes >= cache
    assert mem.temp_size_in_bytes < state / 16       # no state-shaped copy
    for pad_len in traffic_gen.prompt_buckets(
            traffic_gen.load_mix("chat-turnover")):
        pre = bucket(pad_len).compile()
        mem = pre.memory_analysis()
        assert sizing.total_bytes(mem) < sizing.HBM_BYTES, pad_len
        assert mem.alias_size_in_bytes >= cache, pad_len
        assert mem.temp_size_in_bytes < state / 4, pad_len
    assert "grouped_expert_matmul_prefill" in pre.as_text()
    assert "ssm_decode_update" not in pre.as_text()


# ------------------------------------------------------------- the readers
def test_the_state_space_parts_are_read_by_the_one_reader():
    """Since PR 41 the part names are data (``layer_metrics/parts/``) and
    this cell's by-part metrics run the reader every cell runs."""
    with open(os.path.join(BENCH, "layer_metrics",
                           "decode_ssm_update_dev_ms.json")) as f:
        assert json.load(f) == {"reader": "_dev_ms_by_part", "args": {
            "program": "^jit_step", "parts": ["ssm_update"]}}
    bench_run.load_reader("decode_ssm_update_dev_ms")    # as run.py does
    reader = model_spec.load_module(os.path.join(
        BENCH, "layer_metrics", "_dev_ms_by_part.py"))
    with open(os.path.join(reader.PARTS_DIR, "state_space.json")) as f:
        own = json.load(f)["parts"]
    assert set(own) <= set(reader.PARTS)
    for name in own:
        assert reader.part_of(f"jit(step)/{name}/mul:") == name
    assert reader.part_of(
        "jit(step)/ssm_update/ssm_decode_update:") == "ssm_update"


OF_CELL = [m for m in BENCHMARK["per_layer"]
           if CELL in m.get("workloads", [])]
OWN = [m for m in OF_CELL if m["workloads"] == [CELL]]


@pytest.mark.parametrize("m", OF_CELL, ids=lambda m: m["name"])
def test_an_entry_that_lists_the_cell_moves_its_metric_and_has_a_reader(m):
    assert m["moves"] in ("output_tokens_per_s", "setup_s"), m["name"]
    assert callable(bench_run.load_reader(m["name"]))
    if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
        assert m["unit"] == "%"


def test_the_cells_own_entries_read_what_its_model_added():
    """A part, a kernel, a pool of this model alone; what it shares with
    the other serve cells stands under their folded names."""
    names = {m["name"] for m in OF_CELL}
    assert {m["name"] for m in OWN} >= {
        "ssm_decode_update_roofline", "decode_ssm_update_dev_ms",
        "decode_ssm_proj_dev_ms", "prefill_ssm_scan_dev_ms",
        "ssm_state_pool_live_pct"}
    assert {"engine_step_ms", "slot_occupancy_pct", "device_idle_pct",
            "decode_step_dev_ms", "overlapped_turn_pct",
            "expert_layer_dev_ms", "grouped_expert_matmul_roofline",
            "paged_decode_roofline", "replica_ready_s",
            "expert_pairs_dropped"} <= names


# the readers of what PR 39 added to the program (a part, a kernel, a
# counter, a pool): the entries that list this cell alone
OF_THIS_PR = [m["name"] for m in OWN]


@pytest.mark.parametrize("name", OF_THIS_PR)
def test_a_new_reader_finds_nothing_on_another_program_and_does_not_raise(
        name, tmp_path):
    """A run of a cell whose program has none of this PR's spans,
    counters, kernels or pools (the parent's, under these files): None,
    never an exception."""
    empty = tmp_path / "none.xplane.pb"
    empty.write_bytes(b"")
    stats = {"stats": {"steps": 10, "kv_blocks_total": 8, "kv_blocks_free": 4,
                       "kv_block_size": 64, "active_slots": 2,
                       "kv_pools": {"full": {"blocks_total": 8,
                                             "blocks_free": 4}}},
             "now": 1.0}
    run = {"cell": {"name": "none", "chips": 1}, "spec": SPEC,
           "cellfile": _cell(), "mix": {}, "seconds": 3,
           "peaks": {"hbm_bytes_per_s": 8.19e11, "bf16_flops_per_s": 1.97e14},
           "raw": {"open": stats, "close": dict(stats, now=4.0)},
           "trace": {"xplane": str(empty), "programs": {}, "ops": {},
                     "busy_s": 1.0, "window_s": 2.0}}
    assert bench_run.load_reader(name)(run) is None


def test_ssm_state_pool_live_pct_reads_the_slots_that_hold_state():
    reader = bench_run.load_reader("ssm_state_pool_live_pct")

    def stats(live):
        return {"stats": {"kv_pools": {"ssm_state": {
            "slots_total": 192, "slots_live": live,
            "bytes_per_slot": 21_278_720}}}, "now": live}

    run = {"raw": {"open": stats(192), "close": stats(96)}}
    assert reader(run) == pytest.approx(75.0)
    assert reader({"raw": {}}) is None                   # no serve cell
    assert reader({"raw": {"open": {"stats": {}, "now": 0},
                           "close": {"stats": {}, "now": 1}}}) is None


# ------------------------------------------------------------- a rehearsal
TINY = dict(
    SPEC, name="tiny-nemotron", source="test",
    limits="benchmark/limits/tiny-nemotron.json",
    vocab_size=256, hidden_size=64, mamba_num_heads=8, mamba_head_dim=16,
    n_groups=2, ssm_state_size=16, chunk_size=16, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, n_routed_experts=16,
    router_width=16, num_experts_per_tok=4, moe_latent_size=32,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
    max_position_embeddings=1024, reduced=[])
TINY.pop("published")
SSM_METRICS = ("ssm_state_pool_live_pct", "expert_pairs_per_step",
               "expert_load_max_over_mean", "expert_pairs_dropped",
               "engine_step_ms", "slot_occupancy_pct",
               "overlapped_turn_pct")


def test_a_tiny_configuration_of_the_block_runs_through_the_harness(
        tmp_path):
    """On the CPU (pretend chip, nothing it prints is a measurement):
    the adapter, the reference, the check through both caches and the
    cell's counter metrics work end to end through ``run.py``; the
    readers of kernels find no kernel here and leave their metrics out
    without raising."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "ray_tpu"), tmp_path / "ray_tpu")
    bench = json.loads(json.dumps(BENCHMARK))

    def put(rel, obj):
        (tmp_path / "benchmark" / rel).write_text(json.dumps(obj))

    put("configs/tiny-nemotron.json", TINY)
    put("limits/tiny-nemotron.json", {"limits": {
        "serve_prefill_logits_rel_err": {"limit": 0.3},
        "serve_decode_logits_rel_err": {"limit": 0.3}}})
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
        "name": "tiny-nemotron", "source": "test",
        "file": "benchmark/configs/tiny-nemotron.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-cell", "config": "tiny-nemotron",
        "traffic": "tiny-mix", "chips": 1, "why": "test"})
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
    assert set(SSM_METRICS) <= set(got), sorted(got)
    assert got["expert_pairs_dropped"]["value"] == 0
    # every expert is held: 4 pairs for each running slot, at most 3 slots
    assert 0 < got["expert_pairs_per_step"]["value"] <= 12
    assert 0 < got["ssm_state_pool_live_pct"]["value"] <= 100
    assert "ssm_decode_update_roofline" not in got       # no kernel here
    assert "read router_choices_flipped_by_bf16_activations" in proc.stdout
