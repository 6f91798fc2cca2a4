"""A configuration, a traffic mix, a cell, a per-layer metric and an
ARCHITECTURE (adapter, reference, limits, a deployment key of its own, a
part of its block with a name of its own) are each added as NEW files and
entries: in a temp copy of the
benchmark, nothing that was there is edited, and ``run.py`` finds all of
them by name. The run is a rehearsal on the CPU at a tiny size (pretend
chip, the line names the cpu). A configuration whose adapter, reference
or limits file is missing exits with the path that was looked for."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

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


TINY = {
    "name": "new-tiny", "source": "test", "architecture": "dense_decoder",
    "reference": "benchmark/reference/dense_decoder.py",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "max_position_embeddings": 1024,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "reduced": []}
DEPLOYMENT = {"num_slots": 3, "max_seq": 512, "kv_block_size": 64,
              "kv_pool_tokens": 1024, "max_ongoing_requests": 16}
MIX = {"kind": "closed_loop_handle", "clients": 6, "block": 16,
       "prompt_len": {"dist": "uniform", "min": 40, "max": 100},
       "output_len": {"dist": "fixed", "value": 6, "min": 6, "max": 6},
       "temperature": 0.0, "lead_s": 1.0, "drain_s": 30.0,
       "trace_offset_s": 0.5, "trace_s": 1.0}
# the other block's engine takes its pool in blocks, a deployment key
# that only its adapter knows
OTHER_ENGINE = '''

_dense_engine_kwargs = engine_kwargs


def engine_kwargs(spec, deployment):
    return _dense_engine_kwargs(spec, dict(
        deployment, kv_pool_tokens=deployment["pool_blocks"]
        * deployment["kv_block_size"]))
'''


def _copy(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns(
        ".out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "ray_tpu"), tmp_path / "ray_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _put(tmp_path, rel, obj):
    path = tmp_path / "benchmark" / rel
    assert not path.exists()
    path.parent.mkdir(exist_ok=True)
    path.write_text(obj if isinstance(obj, str) else json.dumps(obj))


def _enter(bench):
    """The new configuration and its cell, as entries of the list."""
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


def _run(tmp_path, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "new-cell",
         "--seed", "2147483999", "--seconds", "3", "--trace", str(trace),
         "--rehearse"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=600)


def _dense(tmp_path):
    """A configuration of the block that is there."""
    _put(tmp_path, "configs/new-tiny.json", TINY)
    _put(tmp_path, "cells/new-cell.json", {"deployment": DEPLOYMENT})
    return ({"configs/new-tiny.json", "cells/new-cell.json"},
            "limit 0.06 ok", [])


def _other_block(tmp_path):
    """A new ARCHITECTURE: its adapter (a copy of the dense one under
    another name: the test is of the lookup, not of a block) with a
    deployment key of its own, its reference, its limits, and a part of
    its block that no model before it had (``other_mix``): a file under
    ``layer_metrics/parts/`` and an alias of the by-part reader."""
    with open(os.path.join(BENCH, "architectures", "dense_decoder.py")) as f:
        _put(tmp_path, "architectures/other_block.py", f.read() + OTHER_ENGINE)
    with open(os.path.join(BENCH, "reference", "dense_decoder.py")) as f:
        _put(tmp_path, "reference/other_block.py", f.read())
    with open(os.path.join(BENCH, "limits.json")) as f:
        limits = json.load(f)
    for entry in limits["limits"].values():
        entry["limit"] = 0.03      # this size on the CPU reads under 0.015
    _put(tmp_path, "limits/new-tiny.json", limits)
    _put(tmp_path, "configs/new-tiny.json", dict(
        TINY, architecture="other_block",
        reference="benchmark/reference/other_block.py",
        limits="benchmark/limits/new-tiny.json"))
    deployment = dict(DEPLOYMENT, pool_blocks=16)
    del deployment["kv_pool_tokens"]
    _put(tmp_path, "cells/new-cell.json", {"deployment": deployment})
    _put(tmp_path, "layer_metrics/parts/other_block.json",
         {"why": "test", "parts": ["other_mix"]})
    _put(tmp_path, "layer_metrics/decode_other_mix_dev_ms.json",
         {"reader": "_dev_ms_by_part",
          "args": {"program": "^jit_step", "parts": ["other_mix"]}})
    return ({"configs/new-tiny.json", "cells/new-cell.json",
             "architectures/other_block.py", "reference/other_block.py",
             "limits/new-tiny.json", "layer_metrics/parts/other_block.json",
             "layer_metrics/decode_other_mix_dev_ms.json"},
            "limit 0.03 ok", ["decode_other_mix_dev_ms"])


# one run of the decode step whose one operation lies under the new part
OTHER_MIX_CAPTURE = '''
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
          events { metadata_id: 2 offset_ps: 0 duration_ps: 60000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_step(11)" } }
  event_metadata { key: 2 value {
    id: 2 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop"
    stats { metadata_id: 9 str_value: "jit(step)/other_mix/dot_general:" } } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
'''
READ_THE_NEW_PART = '''
import sys
sys.path.insert(0, ".")
from benchmark import run
run_ = {"trace": {"xplane": sys.argv[1]}, "cell": {"name": "none"}}
print("READ", run.load_reader("decode_other_mix_dev_ms")(run_),
      run.load_reader("decode_dense_mlp_dev_ms")(run_))
'''


def _reads_the_new_part(tmp_path):
    """On the CPU a capture has no device plane and the alias reads
    nothing; on a hand-made capture of a chip the copy's reader files
    the operation under the name its new file brought."""
    from jaxlib._profile_data import ProfileData

    capture = tmp_path / "other_mix.xplane.pb"
    capture.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        OTHER_MIX_CAPTURE))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "-c", READ_THE_NEW_PART, str(capture)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    said = [ln for ln in proc.stdout.splitlines() if ln.startswith("READ")]
    assert said == ["READ 0.06 None"], proc.stdout[-2000:]


@pytest.mark.parametrize("block", [_dense, _other_block])
def test_a_new_cell_is_files_and_entries_only(tmp_path, block):
    bench = _copy(tmp_path)
    before = _hashes(tmp_path / "benchmark")
    added, compared, own_metrics = block(tmp_path)
    _put(tmp_path, "traffic/new-mix.json", MIX)
    _put(tmp_path, "layer_metrics/new_metric.py",
         "def read(run):\n    return float(run['raw']['close']['stats']"
         "['steps'] - run['raw']['open']['stats']['steps'])\n")
    _put(tmp_path, "layer_metrics/new_alias.json",
         {"reader": "_engine_step_ms"})
    _enter(bench)
    for name in ["new_metric", "new_alias"] + own_metrics:
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "engine loop",
            "moves": "output_tokens_per_s", "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    def run(trace):
        proc = _run(tmp_path, trace)
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
        return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout

    line, said = run(0)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"      # never a device number
    # the limits are the file's that the configuration names
    assert said.count(compared) == 2, said[-3000:]
    traced, _ = run(1)
    assert traced["metrics"]["new_metric"]["value"] > 0
    assert traced["metrics"]["new_alias"]["unit"] == "count"
    assert "compiles_in_window" in traced["metrics"]    # no `workloads` key
    assert "ttft_p95_ms" not in traced["metrics"]
    if own_metrics:
        assert not set(own_metrics) & set(traced["metrics"])   # a CPU
        _reads_the_new_part(tmp_path)
    after = _hashes(tmp_path / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == added | {
        "traffic/new-mix.json", "layer_metrics/new_metric.py",
        "layer_metrics/new_alias.json"}


@pytest.mark.parametrize("missing, looked_for", [
    ({"architecture": "nowhere_block"},
     "benchmark/architectures/nowhere_block.py"),
    ({"reference": "benchmark/reference/nowhere.py"},
     "benchmark/reference/nowhere.py"),
    ({"limits": "benchmark/limits/nowhere.json"},
     "benchmark/limits/nowhere.json")], ids=["adapter", "reference",
                                             "limits"])
def test_a_missing_piece_fails_by_the_path_looked_for(tmp_path, missing,
                                                      looked_for):
    bench = _copy(tmp_path)
    _put(tmp_path, "configs/new-tiny.json", dict(TINY, **missing))
    bench["configs"].append({
        "name": "new-tiny", "source": "test",
        "file": "benchmark/configs/new-tiny.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "new-cell", "config": "new-tiny", "traffic": "batch-decode",
        "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = _run(tmp_path, 0)
    assert proc.returncode != 0
    assert str(tmp_path / looked_for) in proc.stderr, proc.stderr[-2000:]
    assert not proc.stdout.strip().startswith("{")


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
