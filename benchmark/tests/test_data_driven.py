"""A configuration, a traffic mix, a cell and a per-layer metric are each
added as NEW files and entries: in a temp copy of the benchmark, nothing
that was there is edited, and ``run.py`` finds all of them by name. The
run is a rehearsal on the CPU at a tiny size (pretend chip, the line
names the cpu)."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _hashes(folder):
    out = {}
    for base, dirs, files in os.walk(folder):
        dirs[:] = [d for d in dirs if d not in (".out", "__pycache__")]
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, folder)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns(
        ".out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "ray_tpu"), tmp_path / "ray_tpu")
    before = _hashes(tmp_path / "benchmark")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def put(rel, obj):
        path = tmp_path / "benchmark" / rel
        assert not path.exists()
        path.write_text(obj if isinstance(obj, str) else json.dumps(obj))

    put("configs/new-tiny.json", {
        "name": "new-tiny", "source": "test", "architecture": "dense_decoder",
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256, "max_position_embeddings": 1024,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": False, "torch_dtype": "bfloat16",
        "reduced": []})
    put("traffic/new-mix.json", {
        "kind": "closed_loop_handle", "clients": 6, "block": 16,
        "prompt_len": {"dist": "uniform", "min": 40, "max": 100},
        "output_len": {"dist": "fixed", "value": 6, "min": 6, "max": 6},
        "temperature": 0.0, "lead_s": 1.0, "drain_s": 30.0,
        "trace_offset_s": 0.5, "trace_s": 1.0})
    put("cells/new-cell.json", {"deployment": {
        "num_slots": 3, "max_seq": 512, "kv_block_size": 64,
        "kv_pool_tokens": 1024, "max_ongoing_requests": 16}})
    put("layer_metrics/new_metric.py",
        "def read(run):\n    return float(run['raw']['close']['stats']"
        "['steps'] - run['raw']['open']['stats']['steps'])\n")
    put("layer_metrics/new_alias.json", {"reader": "_engine_step_ms"})
    bench["configs"].append({
        "name": "new-tiny", "source": "test",
        "file": "benchmark/configs/new-tiny.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "new-cell", "config": "new-tiny", "traffic": "new-mix",
        "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "output_tokens_per_s":
            m["workloads"].append("new-cell")
    for name in ("new_metric", "new_alias"):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "engine loop",
            "moves": "output_tokens_per_s", "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)

    def run(trace):
        proc = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", "new-cell",
             "--seed", "2147483999", "--seconds", "3", "--trace", str(trace),
             "--rehearse"], cwd=tmp_path, env=env, capture_output=True,
            text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    line = run(0)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"      # never a device number
    traced = run(1)
    assert traced["metrics"]["new_metric"]["value"] > 0
    assert traced["metrics"]["new_alias"]["unit"] == "count"
    assert "compiles_in_window" in traced["metrics"]    # no `workloads` key
    assert "ttft_p95_ms" not in traced["metrics"]
    after = _hashes(tmp_path / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/new-tiny.json", "traffic/new-mix.json",
        "cells/new-cell.json", "layer_metrics/new_metric.py",
        "layer_metrics/new_alias.json"}


def test_without_the_program_the_command_fails_and_prints_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns(
        ".out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "train-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")
