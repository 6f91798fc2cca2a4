"""Compile each cell's programs for a described (not attached) v5e:2x2 at
the cell's real shapes, and see that they fit: the decode step and the
2048 prefill bucket of mistral-7b-l16, and both train steps (the
four-chip one on a Mesh of the described devices).

Nothing runs. The topology is described inside a fixture of THIS file
and every compile happens in the test's own process (on-chip-measurement
guide, section 2).
"""

import json
import os

import jax
import pytest

from benchmark import model_spec, sizing, traffic_gen


def _cell(name):
    with open(os.path.join(model_spec.HERE, "cells", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def devices(topo):
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep these tests silent
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return topo.devices


@pytest.fixture
def as_tpu(monkeypatch):
    """The kernel dispatchers ask ``jax.default_backend()`` at trace
    time; here it says cpu. Steered from the test, not by a program
    option."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_serve_programs_fit_one_chip(devices, as_tpu):
    spec = model_spec.load_config("mistral-7b-l16")
    dep = _cell("serve-chat-steady")["deployment"]
    assert _cell("serve-batch-decode")["deployment"] == dep
    decode, bucket = sizing.serve_programs(spec, dep, devices[0])
    compiled = decode.compile()
    assert "tpu_custom_call" in compiled.as_text()      # the paged kernel
    assert sizing.total_bytes(compiled.memory_analysis()) < sizing.HBM_BYTES
    largest = max(traffic_gen.prompt_buckets(
        traffic_gen.load_mix("chat-steady")))
    assert largest == 2048
    mem = bucket(largest).compile().memory_analysis()
    assert sizing.total_bytes(mem) < sizing.HBM_BYTES
    weights_and_pool = (2 * model_spec.num_params(spec)
                        + dep["kv_pool_tokens"] * spec["num_hidden_layers"]
                        * model_spec.kv_bytes_per_token(spec))
    assert mem.argument_size_in_bytes >= weights_and_pool


# The four-chip cell is not in BENCHMARK.json yet (PERF.md, Open
# questions): its job as it was sized in PR 23, 16 sequences on fsdp=2 x
# tp=2 (20 are refused by 111 MB).
FSDP2TP2 = {"batch": 16, "fsdp": 2, "tp": 2}


@pytest.mark.parametrize("job, config", [
    ("train-1chip", "deepseek-coder-1.3b"),
    (FSDP2TP2, "mistral-7b-l16")], ids=["train-1chip", "train-fsdp2tp2"])
def test_train_step_compiles_inside_the_device_limit(devices, as_tpu, job,
                                                     config):
    spec = model_spec.load_config(config)
    job = dict(_cell(job)["job"] if isinstance(job, str) else job, seq=4096)
    chips = job.get("fsdp", 1) * job.get("tp", 1)
    lowered = sizing.train_program(spec, job, devices[:chips], job["batch"])
    compiled = lowered.compile()      # refuses what does not fit 15.75 GiB
    text = compiled.as_text()
    assert "tpu_custom_call" in text                    # the flash kernels
    if chips > 1:
        assert "all-reduce" in text or "all-gather" in text
