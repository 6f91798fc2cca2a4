"""Test configuration.

Tests run on CPU with a virtual 8-device mesh (the reference tests distributed
behavior on one machine with multi-raylet localhost clusters, SURVEY.md §4; we
do the same and additionally virtualize chips for sharding tests).
Must set env vars BEFORE jax is imported anywhere.
"""

import os

# Hard-set (not setdefault): the outer environment may point JAX_PLATFORMS at
# a real TPU (the chip machine sets "tpu,cpu"); the tests never take a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()

# One forkserver per raylet in tests: the production default (2) exists
# for sustained actor churn — fork(2) parallelism — but every test
# cluster init would pay a second warm-interpreter boot (~2 s CPU) for
# pools it never stresses, and the suite runs hundreds of cluster
# inits against a hard wall-clock budget. MultiFactoryClient logic is
# identical at K=1.
os.environ.setdefault("RT_worker_factory_procs", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.default_backend() == "cpu", (
    "tests must run on the virtual CPU mesh, got " + jax.default_backend())
assert jax.device_count() == 8

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (-m 'not slow'); run "
        "explicitly, e.g. the 500k queued-task envelope")


@pytest.fixture(autouse=True, scope="session")
def _fresh_natives():
    """Rebuild stale native extensions BEFORE any test imports them.

    The runtime loaders rebuild on mtime staleness but swallow compile
    errors and fall back to pure-Python paths — a session running against
    a stale or unbuildable .so silently measures the wrong codec.  The
    script fails loudly instead; a broken native build should fail the
    session, not degrade it."""
    import subprocess

    script = os.path.join(os.path.dirname(__file__), "..", "scripts",
                          "build_natives.sh")
    proc = subprocess.run(["bash", script], capture_output=True, text=True,
                          timeout=300)
    if proc.returncode != 0:
        pytest.exit(f"native extension build failed:\n{proc.stdout}"
                    f"\n{proc.stderr}", returncode=3)
    yield


@pytest.fixture
def kernel_on_cpu(monkeypatch):
    """Every dispatcher under ``ray_tpu/ops`` asks
    ``ops.attention.on_tpu()`` whether to build a work list and call its
    kernel; here it says no. Steer them all from the test, through that
    one lookup: each kernel, interpreted."""
    import functools

    from ray_tpu.ops import attention
    from ray_tpu.ops.pallas import (decode_attention, expert_combine,
                                    flash_attention, grouped_matmul,
                                    paged_decode_attention,
                                    paged_hybrid_decode_attention,
                                    paged_mla_decode_attention,
                                    ssm_decode_update)

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    for module, kernel in [
            (decode_attention, "decode_attention"),
            (expert_combine, "expert_combine"),
            (flash_attention, "flash_attention_fwd_pallas"),
            (flash_attention, "flash_attention_bwd_pallas"),
            (grouped_matmul, "grouped_matmul"),
            (grouped_matmul, "grouped_matmul_dw"),
            (paged_decode_attention, "paged_decode_attention"),
            (paged_hybrid_decode_attention, "paged_hybrid_decode_attention"),
            (paged_mla_decode_attention, "paged_mla_decode_kernel"),
            (ssm_decode_update, "ssm_decode_update")]:
        monkeypatch.setattr(module, kernel, functools.partial(
            getattr(module, kernel), interpret=True))


@pytest.fixture
def local_cluster():
    """A started single-node framework instance, shut down after the test."""
    import ray_tpu

    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=4, resources={"TPU": 0})
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(autouse=True, scope="module")
def _module_isolation_guard():
    """Between test FILES: a leaked initialized instance changes later
    files' topology, and stray worker/factory processes from an unclean
    shutdown compound until the monolithic run crawls (round-2 finding:
    `pytest tests -q` didn't terminate in 40 min while per-file runs took
    13). Shut down anything left and reap stray children."""
    yield
    import ray_tpu

    try:
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
    except Exception:  # noqa: BLE001 — guard must never fail the module
        pass
    _kill_own_strays()


# Every process a test of THIS pytest process starts inherits the marker
# (drivers, raylets, factories and the workers they fork all copy the
# environment). The tier-1 run has several pytest workers side by side:
# killing strays by command line alone kills the other workers' clusters.
_OWNER = f"RT_TEST_OWNER={os.getpid()}".encode()
os.environ["RT_TEST_OWNER"] = str(os.getpid())


def _kill_own_strays():
    import signal

    patterns = (b"ray_tpu.core_worker.worker_main",
                b"ray_tpu.raylet.worker_factory")
    for entry in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                cmdline = f.read()
            if not any(p in cmdline for p in patterns):
                continue
            with open(f"/proc/{entry}/environ", "rb") as f:
                if _OWNER not in f.read().split(b"\0"):
                    continue
            os.kill(int(entry), signal.SIGTERM)
        except OSError:
            continue            # gone meanwhile, or not ours to read
