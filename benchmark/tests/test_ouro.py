"""What PR 52 added to the benchmark as new files: the ``ouro`` adapter's
counts against hand counts, the configuration's file against the
catalog's published keys, the ``loop-reason-decode`` mix, the cell's
programs compiled for a described v5e, the three new readers, and a
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
NAME = "ouro-2.6b"
CELL = "serve-looped-dense-decode"
SPEC = model_spec.load_config(NAME)
ARCH = model_spec.adapter(SPEC)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)


def _cell(name=CELL):
    with open(os.path.join(BENCH, "cells", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ counts
def test_parameters_of_the_whole_model():
    attention = 4 * 2048 * 2048
    mlp = 3 * 2048 * 5632
    layer = attention + mlp + 4 * 2048
    assert (attention, mlp, layer) == (16_777_216, 34_603_008, 51_388_416)
    whole = 48 * layer + 2 * 49_152 * 2048 + 2 * 2048 + 1
    assert model_spec.num_params(SPEC) == whole == 2_667_974_657
    assert abs(whole / 2.67e9 - 1) < 0.01
    assert round(2 * whole / 1024 ** 3, 2) == 4.97       # GiB in bfloat16
    assert model_spec.matrix_params(SPEC) == {
        "per_layer": attention + mlp, "layers": 48 * (attention + mlp),
        "head": 2048 * 49_152}
    # one pool layer's bytes a token, and the 192 of them
    assert model_spec.kv_bytes_per_token(SPEC) == 2 * 16 * 128 * 2 == 8192
    assert ARCH.pool_layers(SPEC) == 192
    assert 192 * 8192 == 1_572_864 == 1.5 * 1024 ** 2


def test_the_weights_tree_holds_the_models_parameters():
    leaves = jax.tree.leaves(ARCH.weight_shapes(SPEC),
                             is_leaf=lambda t: isinstance(t, tuple))
    assert sum(math.prod(s) for s in leaves) == model_spec.num_params(SPEC)
    std, stds = ARCH.weight_stds(SPEC)
    assert std == 2048 ** -0.5
    assert {"attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm",
            "final_norm"} <= set(stds)
    assert "wo" not in stds and "w_down" not in stds    # a norm follows
    # every pass starts six times wider than a sublayer's step (PR 52)
    assert stds["embed"] == stds["final_norm"] == ARCH.LOOP_SCALE == 6.0
    assert stds["exit_w"] == std / 6.0


def test_one_kernel_calls_bytes_and_a_whole_steps():
    one = 4700 * 8192 + 2 * 16 * 2048 * 2
    assert model_spec.kernel_counts(SPEC, "paged_decode_attention",
                                    live_tokens=4700, slots=16) == {
        "bytes": one}
    with pytest.raises(KeyError, match="no kernel named"):
        model_spec.kernel_counts(SPEC, "paged_mla_decode")
    layers = 2 * 48 * 51_388_416
    step = ARCH.decode_step_bytes(SPEC, 4700, 16)
    assert step == (4 * (layers + 2 * 4097) + 2 * 2048 * 49_152
                    + 16 * 2048 * 2 + 4700 * 1_572_864 + 16 * 1_572_864
                    + 16 * 49_152 * 4)
    # four reads of the weights are most of it, the cache the rest
    assert 0.70 < 4 * layers / step < 0.74
    assert 0.26 < 4700 * 1_572_864 / step < 0.28
    assert 192 * one < step
    for name, args in (("train_flops_per_token", (4096,)),
                       ("train_setup", (None, None)),
                       ("train_program_loss_and_grads", (None, None))):
        with pytest.raises(SystemExit, match="no train path"):
            getattr(ARCH, name)(SPEC, *args)
    with pytest.raises(SystemExit, match="no backward pass"):
        model_spec.reference(SPEC).last_block_loss_and_grads(None, None,
                                                             SPEC)


# ------------------------------------------------------- the configuration
def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "Ouro-2.6B")


def test_the_file_keeps_every_published_key_and_cuts_nothing():
    assert SPEC["reduced"] == []
    want = {"head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
            "num_attention_heads": 16, "num_hidden_layers": 48,
            "num_key_value_heads": 16, "vocab_size": 49152,
            "max_position_embeddings": 65536, "rope_theta": 1000000,
            "rms_norm_eps": 1e-06, "total_ut_steps": 4,
            "early_exit_threshold": 1, "tie_word_embeddings": False,
            "model_type": "ouro", "hidden_act": "silu"}
    for key, value in want.items():
        assert SPEC[key] == SPEC["published"][key] == value, key
    for key, value in SPEC["published"].items():
        assert SPEC[key] == value, key
    row = _catalog()
    assert SPEC["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert SPEC[key] == value, key
    assert {"four_norms", "norm_between_passes", "exit_gate", "no_bias",
            "cache_index", "torch_dtype"} <= set(SPEC["assumed"])
    assert "published modeling code" in SPEC["assumed"][
        "source_of_what_follows"]
    assert "the whole model" in SPEC["deployment"]
    entry = [c for c in BENCHMARK["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == [] and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == SPEC["source"]
    assert ARCH.program_kwargs(SPEC) == dict(
        vocab_size=49152, hidden=2048, n_layers=48, n_heads=16,
        n_kv_heads=16, head_dim=128, mlp_dim=5632, max_seq=65536,
        rope_theta=1e6, norm_eps=1e-6, total_ut_steps=4,
        early_exit_threshold=1.0)


# what no other configuration has: the looped block's own mechanism
OWN = ("loop_step_hbm_roofline", "kv_pool_live_pct", "expected_exit_pass",
       "decode_post_norm_dev_ms", "decode_exit_gate_dev_ms")
# the readings of the engine, the decode step's parts that this block has
# (``parts/base.json``) and the prefill that it shares with other cells,
# by their names up to a tag: the cell's PR brought `<name>.loop`, a fold
# takes a tag off, and neither is this test's business
SHARED = ("replica_ready_s", "slot_occupancy_pct", "engine_step_ms",
          "decode_step_dev_ms", "device_idle_pct", "overlapped_turn_pct",
          "paged_decode_roofline", "paged_decode_dev_ms",
          "prefill_dev_share_pct", "decode_attn_proj_dev_ms",
          "decode_dense_mlp_dev_ms", "decode_head_dev_ms",
          "decode_kv_store_dev_ms", "decode_unnamed_dev_ms",
          "prefill_attention_dev_ms", "prefill_unnamed_dev_ms",
          "turn_decode_wait_ms", "turn_prefill_wait_ms", "turn_host_ms",
          "prompts_per_admitting_turn", "gap_after_prefill_ms",
          "gap_in_turn_ms", "other_programs_dev_ms")


def _parts(name):
    with open(os.path.join(BENCH, "layer_metrics", "parts",
                           name + ".json")) as f:
        return json.load(f)["parts"]


def _mine():
    """The per-layer entries that list the cell."""
    return [m for m in BENCHMARK["per_layer"]
            if CELL in m.get("workloads", ())]


def test_the_cell_and_the_lists_it_joins():
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    assert cells[CELL] == dict(cells[CELL], config=NAME, chips=1,
                               traffic="loop-reason-decode")
    assert len(cells[CELL]["why"]) <= 200
    lists = {m["name"]: m.get("workloads")
             for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]}
    assert CELL in lists["output_tokens_per_s"]
    # the two that move setup_s list no cells: reported here as everywhere
    for name in ("compiles_in_window", "peak_hbm_gb"):
        assert lists[name] is None
    assert {m["moves"] for m in _mine()} == {"output_tokens_per_s",
                                             "setup_s"}
    # no entry the cell lists reads a part of another block's
    parts = {name: _parts(name) for name in ("base", "ouro")}
    from ray_tpu.util import profiling

    assert tuple(parts["ouro"]) == profiling.LOOP_PARTS
    known = {"unnamed", *parts["base"], *parts["ouro"]}
    for m in _mine():
        reader, args = fold.resolved(ROOT, m["name"])
        if reader == "_dev_ms_by_part":
            assert set(args.get("parts", ())) <= known, m["name"]


@pytest.mark.parametrize("name", OWN)
def test_the_cell_reports_an_entry_of_its_own_mechanism(name):
    assert [m for m in _mine() if m["name"] == name], name
    assert callable(bench_run.load_reader(name))


@pytest.mark.parametrize("base", SHARED)
def test_the_cell_is_in_the_list_of_a_shared_entry(base):
    """Under whatever tag, once: one entry of that reading lists it."""
    assert len([m for m in _mine()
                if m["name"].split(".")[0] == base]) == 1, base


def test_a_configuration_that_is_not_this_block_exits_by_name(monkeypatch):
    with pytest.raises(SystemExit, match="needs the keys"):
        ARCH.check_config({k: v for k, v in SPEC.items()
                           if k != "total_ut_steps"})
    with pytest.raises(SystemExit, match="untied head"):
        ARCH.check_config(dict(SPEC, tie_word_embeddings=True))
    with pytest.raises(SystemExit, match="not 2.67 B"):
        ARCH.check_config(dict(SPEC, published=dict(
            SPEC["published"], num_hidden_layers=24)))
    # a checkout from before the block: refused in the driver, by the
    # module's path, before anything is started
    monkeypatch.setattr(os.path, "isfile",
                        lambda p: not p.endswith("models/ouro.py"))
    with pytest.raises(SystemExit, match=r"no file .*ray_tpu/models/ouro"):
        ARCH.check_config(SPEC)


def test_the_limits_fail_the_control_and_pass_the_program():
    lim = model_spec.limits(SPEC)
    assert set(lim) == {"serve_prefill_logits_rel_err",
                        "serve_decode_logits_rel_err"}
    for entry in lim.values():
        assert entry["program_largest"] < entry["limit"]
        assert entry["seeds"] >= 12 and entry["control_seeds"] >= 12
        assert entry["limit"] < entry["control_int8_smallest"]


# ------------------------------------------------------------- the traffic
def test_loop_reason_decode_sends_the_same_lengths_for_every_seed():
    mix = traffic_gen.load_mix("loop-reason-decode")
    dep = _cell()["deployment"]
    assert mix["kind"] == "closed_loop_handle"
    assert mix["clients"] == mix["block"] == 32 == 2 * dep["num_slots"]
    assert mix["temperature"] == 0.0 and mix["shared_prefix_tokens"] == 0
    assert (mix["trace_offset_s"], mix["trace_s"]) == (8.0, 4.0)
    shapes = []
    for seed in (1, 2_147_483_999, 3_000_000_000):
        stream = traffic_gen.request_stream(mix, seed, SPEC["vocab_size"])
        reqs = [next(stream) for _ in range(64)]
        assert all(0 <= t < 49152 for r in reqs for t in r["prompt"])
        shapes.append([(len(r["prompt"]), r["max_tokens"]) for r in reqs])
    assert shapes[0] == shapes[1] == shapes[2]
    plens = sorted(p for p, _ in shapes[0][:32])
    olens = [o for _, o in shapes[0][:32]]
    assert plens[0] >= 64 and plens[-1] <= 384
    assert 120 <= plens[16] <= 136                       # median 128
    assert 140 <= sum(plens) / 32 <= 155                 # mean about 147
    assert min(olens) >= 128 and max(olens) <= 384
    assert sum(olens) / 32 == 256
    assert max(p + o for p, o in shapes[0]) <= 768 <= dep["max_seq"]
    assert traffic_gen.prompt_buckets(mix) == [64, 128, 256, 512]
    # the pool holds sixteen slots at the mean (prompt + half an answer)
    # and their half-empty last blocks at more than two deviations
    mean = 16 * (sum(plens) / 32 + 128) + 16 * dep["kv_block_size"] / 2
    assert mean * 1.25 < dep["kv_pool_tokens"]
    assert dep["kv_pool_tokens"] >= 5632


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
    """The decode step at 16 slots and the smallest and largest prefill
    buckets: ONE paged decode kernel call in the one loop over the 192
    pool layers, both pools donated and updated in place (no pool-sized
    and no weight-sized temporary: as two loops over the same stacked
    weights the compiler laid wq and wk out anew, 0.75 GiB), each weight
    read where it lies, and the sums leave room for the check."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dep = _cell()["deployment"]
    step, bucket = sizing.serve_programs(SPEC, dep, device)
    compiled = step.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "paged_decode_attention" in text
    assert not [line for line in text.splitlines()
                if ".remat" in line and "params__" in line]
    assert not [line for line in text.splitlines()
                if " copy(%params__layers" in line]
    bs = dep["kv_block_size"]
    pool = 2 * 192 * (1 + dep["kv_pool_tokens"] // bs) * bs * 2048 * 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= \
        2 * model_spec.num_params(SPEC) + pool
    assert mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < 64 * 1024 ** 2
    total = sizing.total_bytes(mem)
    assert 0.85 * sizing.HBM_BYTES < total          # 13.6 GiB and more
    assert total < 15.75 * 1024 ** 3 - 0.75 * 1024 ** 3
    for pad_len in (64, 512):
        mem = bucket(pad_len).compile().memory_analysis()
        assert mem.alias_size_in_bytes >= pool
        assert mem.temp_size_in_bytes < 64 * 1024 ** 2
        assert sizing.total_bytes(mem) < 15.0 * 1024 ** 3


# ------------------------------------------------------------- the readers
def _stats(free, exits, rows):
    return {"stats": {"kv_blocks_total": 192, "kv_blocks_free": free,
                      "kv_block_size": 32, "active_slots": 16,
                      "model_counters": dict(
                          {f"exit_p{t}": e for t, e in enumerate(exits)},
                          exit_rows=rows)}, "now": float(rows)}


def test_the_pool_and_exit_readers():
    live = bench_run.load_reader("kv_pool_live_pct")
    exits = bench_run.load_reader("expected_exit_pass")
    run = {"raw": {"open": _stats(48, [10, 20, 30, 40], 100),
                   "close": _stats(24, [110, 320, 330, 340], 1100)}}
    assert live(run) == pytest.approx(100 * (0.75 + 0.875) / 2)
    # 100, 300, 300, 300 of 1,000 rows: 0.3 + 0.6 + 0.9
    assert exits(run) == pytest.approx(1.8)
    assert live({"raw": {}}) is None and exits({"raw": {}}) is None
    # a program without the counters, or a window with no decode row
    bare = {"stats": {"steps": 3}, "now": 0}
    assert exits({"raw": {"open": bare, "close": bare}}) is None
    assert live({"raw": {"open": bare, "close": bare}}) is None
    same = _stats(48, [10, 20, 30, 40], 100)
    assert exits({"raw": {"open": same, "close": same}}) is None


def test_the_whole_steps_roofline_reader(monkeypatch):
    read = bench_run.load_reader("loop_step_hbm_roofline")
    run = {"spec": SPEC, "cellfile": _cell(),
           "peaks": {"hbm_bytes_per_s": 819e9},
           "raw": {"open": _stats(42, [1] * 4, 4),
                   "close": _stats(42, [2] * 4, 8)},
           "trace": {"programs": {
               "jit_step(123)": {"count": 80.0, "total_s": 3.6,
                                 "median_s": 0.045},
               "jit_prefill(9)": {"count": 4.0, "total_s": 0.1,
                                  "median_s": 0.03}}}}
    tokens = 150 * 32 - 16 * 16
    want = 100 * 80 * ARCH.decode_step_bytes(SPEC, tokens, 16) / 819e9 / 3.6
    assert read(run) == pytest.approx(want) and 70 < want < 80
    assert read(dict(run, trace={"programs": {}})) is None
    assert read(dict(run, raw={})) is None
    # an adapter that counts no whole step (every other one): nothing
    dense = model_spec.load_config("mistral-7b-l16")
    assert read(dict(run, spec=dense)) is None


# ------------------------------------------------------------- a rehearsal
TINY = dict(
    SPEC, name="tiny-ouro", limits="benchmark/limits/tiny-ouro.json",
    vocab_size=256, hidden_size=64, intermediate_size=96,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, max_position_embeddings=1024, reduced=[])
TINY.pop("published")
LOOP_METRICS = ("kv_pool_live_pct", "expected_exit_pass",
                "slot_occupancy_pct", "engine_step_ms",
                "overlapped_turn_pct", "replica_ready_s",
                "compiles_in_window")


def test_a_tiny_configuration_of_the_block_runs_through_the_harness(
        tmp_path):
    """On the CPU (pretend chip, nothing it prints is a measurement):
    the adapter, the reference, the check on the replica's own engine,
    the warm-up's answers of 3 tokens, the probe and the cell's counter
    metrics work end to end through ``run.py``; the readers of kernels
    and of the device's programs find nothing here and leave their
    metrics out without raising."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "ray_tpu"), tmp_path / "ray_tpu")
    bench = json.loads(json.dumps(BENCHMARK))

    def put(rel, obj):
        (tmp_path / "benchmark" / rel).write_text(json.dumps(obj))

    put("configs/tiny-ouro.json", TINY)
    put("limits/tiny-ouro.json", {"limits": {
        "serve_prefill_logits_rel_err": {"limit": 0.15},
        "serve_decode_logits_rel_err": {"limit": 0.15}}})
    put("cells/tiny-cell.json", {"deployment": {
        "num_slots": 3, "max_seq": 512, "kv_block_size": 32,
        "kv_pool_tokens": 1536, "max_ongoing_requests": 16}})
    put("traffic/tiny-mix.json", {
        "kind": "closed_loop_handle", "clients": 6, "block": 16,
        "prompt_len": {"dist": "uniform", "min": 40, "max": 100},
        "output_len": {"dist": "uniform", "min": 5, "max": 11},
        "temperature": 0.0, "lead_s": 1.0, "drain_s": 30.0,
        "trace_offset_s": 0.5, "trace_s": 1.0})
    bench["configs"].append({
        "name": "tiny-ouro", "source": "test",
        "file": "benchmark/configs/tiny-ouro.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-cell", "config": "tiny-ouro", "traffic": "tiny-mix",
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
    assert set(LOOP_METRICS) <= set(got), sorted(got)
    assert 0 < got["kv_pool_live_pct"]["value"] <= 100
    assert 0 < got["expected_exit_pass"]["value"] < 3
    assert "paged_decode_roofline" not in got            # no kernel here
    # a checkout without the program's module: the driver refuses the
    # cell before it starts anything
    os.remove(tmp_path / "ray_tpu")
    os.makedirs(tmp_path / "ray_tpu" / "models")
    gone = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny-cell",
         "--seed", "1", "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=60)
    assert gone.returncode != 0
    assert "no file" in gone.stderr and "ouro.py" in gone.stderr
    assert "bringing up" not in gone.stdout
