"""chip_smoke.py without a chip: it refuses, and its phases are sound.

The script has one path (1b, tpu or fail). Its phase functions are driven
here with the `debug` model on a node that advertises pretend chips, so
faults in the script are found on the CPU: every check up to the platform
check runs, and that check then refuses the CPU worker."""

import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

ARGS = SimpleNamespace(model="debug", seed=0, seq=64, batch=4, steps=4,
                       prompt_len=(20, 60), max_tokens=8)


def test_no_chip_no_result():
    """Where the host offers no chip the script says so, exits non-zero
    and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TPU_VISIBLE_CHIPS", None)
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no chip" in p.stderr and "TPU: 0" in p.stderr
    assert '"ok"' not in p.stdout


@pytest.fixture
def four_pretend_chips(monkeypatch):
    import ray_tpu
    from ray_tpu import serve

    # the workers inherit this: a worker granted all four "chips" sees
    # four CPU devices, so the train loop runs its one-device AND its
    # fsdp=2 x tp=2 half
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    ray_tpu.init(num_cpus=4, num_tpus=4)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_phases_run_then_refuse_a_cpu_worker(four_pretend_chips):
    with pytest.raises(SystemExit, match="no chip: the serve worker "
                                         "reports platform 'cpu'"):
        chip_smoke.serve_phase(ARGS, vocab=256, replicas=1)
    from ray_tpu import serve

    serve.delete(chip_smoke.APP)
    with pytest.raises(SystemExit, match="no chip: the train worker "
                                         "reports platform 'cpu'"):
        chip_smoke.train_phase(ARGS, chips=4)
