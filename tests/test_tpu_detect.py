"""Chip counting and the one-process-for-each-chip environment rule."""

import os

import pytest

from ray_tpu.common import tpu_detect


@pytest.fixture
def no_tpu_env(monkeypatch):
    for k in ("TPU_VISIBLE_CHIPS", "TPU_ACCELERATOR_TYPE",
              "ACCELERATOR_TYPE"):
        monkeypatch.delenv(k, raising=False)


def _dev(tmp_path, nodes):
    for n in nodes:
        path = tmp_path / n
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text("")
    return str(tmp_path)


@pytest.mark.parametrize("nodes,visible,want", [
    (["vfio/0", "vfio/vfio"], None, 1.0),       # control node is no chip
    (["vfio/0", "vfio/1", "vfio/2", "vfio/3", "vfio/vfio"], None, 4.0),
    (["accel0", "accel1", "accel2", "accel3"], None, 4.0),
    (["accel0", "vfio/vfio"], None, 1.0),
    (["null", "vfio/vfio"], None, 0.0),          # no chip at all
    (["vfio/0", "vfio/1", "vfio/2", "vfio/3"], "2", 1.0),   # env wins
    ([], "0,1", 2.0),
    (["vfio/3", "vfio/vfio"], "v5litepod-4", 1.0),   # one chip of a board
    ([], "v5litepod-4", 4.0),
])
def test_detect_counts_chips(tmp_path, monkeypatch, no_tpu_env, nodes,
                             visible, want):
    if visible is not None and visible.startswith("v5"):
        monkeypatch.setenv("TPU_ACCELERATOR_TYPE", visible)
    elif visible is not None:
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", visible)
    assert tpu_detect.detect(dev_root=_dev(tmp_path, nodes))["chips"] == want


def test_detect_never_imports_jax(tmp_path, no_tpu_env):
    import subprocess
    import sys

    code = ("import sys; from ray_tpu.common import tpu_detect; "
            f"tpu_detect.detect(dev_root={str(tmp_path)!r}); "
            "assert 'jax' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=os.path.dirname(os.path.dirname(__file__)))


# ------------------------------------------------ the worker environment rule
@pytest.fixture
def rule(monkeypatch, tmp_path):
    """The rule keeps one piece of per-process state; give each case its
    own. Returns grant(chips, env, modules) on a host with four chips."""
    monkeypatch.setattr(tpu_detect, "_platforms_before_pin",
                        tpu_detect._UNPINNED)
    monkeypatch.setattr(tpu_detect, "granted_chips", None)
    dev = _dev(tmp_path, ["vfio/0", "vfio/1", "vfio/2", "vfio/3",
                          "vfio/vfio"])

    def grant(chips, env, modules=None):
        tpu_detect.grant_chips(chips, env, modules or {}, dev_root=dev)

    return grant


@pytest.mark.parametrize("before", [None, "", "tpu,cpu", "cpu"])
def test_no_lease_pins_cpu(rule, before):
    env = {} if before is None else {"JAX_PLATFORMS": before}
    tpu_detect.pin_cpu_until_granted(env)
    assert env["JAX_PLATFORMS"] == "cpu"


@pytest.mark.parametrize("chips,bounds,port", [
    ([0], "1,1,1", "8476"),
    ([3], "1,1,1", "8479"),
    ([1, 2], "1,2,1", "8477"),
])
def test_lease_lifts_pin_and_shows_exactly_its_chips(rule, chips, bounds,
                                                     port):
    env = {"JAX_PLATFORMS": "tpu,cpu", "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}
    tpu_detect.pin_cpu_until_granted(env)
    rule(chips, env)
    assert env["JAX_PLATFORMS"] == "tpu,cpu"
    assert env["TPU_VISIBLE_CHIPS"] == ",".join(map(str, chips))
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == bounds
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # several one-chip processes on one host: a runtime port for each
    assert env["TPU_PROCESS_PORT"] == port
    assert env["TPU_PROCESS_ADDRESSES"] == f"localhost:{port}"


def test_whole_host_lease_hides_nothing(rule):
    env = {"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}
    tpu_detect.pin_cpu_until_granted(env)
    rule([0, 1, 2, 3], env)
    cache = env.pop("JAX_COMPILATION_CACHE_DIR")     # set with the grant
    assert env == {"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}   # pin lifted too
    assert cache == os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


@pytest.mark.parametrize("placed", [None, "/somewhere/else"])
def test_compile_cache_is_placed_from_outside(placed):
    """Set outside: no directory is set in code. Unset: the fixed
    <checkout>/.jax_cache, also on a jax that is already imported."""
    from ray_tpu.common import compile_cache

    updates = []

    class FakeJax:
        class config:
            update = staticmethod(lambda k, v: updates.append((k, v)))

    env = {} if placed is None else {"JAX_COMPILATION_CACHE_DIR": placed}
    got = compile_cache.use_compile_cache(env, {"jax": FakeJax})
    if placed is not None:
        assert got == placed and not updates
        assert env == {"JAX_COMPILATION_CACHE_DIR": placed}
    else:
        fixed = os.path.join(compile_cache._CHECKOUT, ".jax_cache")
        assert got == fixed == env["JAX_COMPILATION_CACHE_DIR"]
        assert updates == [("jax_compilation_cache_dir", fixed)]


def test_lease_after_jax_import_is_refused(rule):
    env = {}
    tpu_detect.pin_cpu_until_granted(env)
    with pytest.raises(RuntimeError, match="fresh worker"):
        rule([0], env, {"jax": object()})
    assert env["JAX_PLATFORMS"] == "cpu"     # still pinned, never half-lifted


def test_cpu_only_deployment_has_nothing_to_lift(rule):
    """JAX_PLATFORMS=cpu from outside (these tests run so): a grant of
    (fake) chips opens no device, so a worker that already imported jax
    is not refused."""
    env = {"JAX_PLATFORMS": "cpu"}
    tpu_detect.pin_cpu_until_granted(env)
    rule([1], env, {"jax": object()})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["TPU_VISIBLE_CHIPS"] == "1"


def test_empty_grant_keeps_pin(rule):
    env = {}
    tpu_detect.pin_cpu_until_granted(env)
    rule([], env)
    assert env == {"JAX_PLATFORMS": "cpu", "TPU_VISIBLE_CHIPS": ""}


@pytest.mark.parametrize("before", [{}, {"JAX_PLATFORMS": "tpu,cpu"}])
def test_leaseless_child_env(before):
    assert tpu_detect.leaseless_env(dict(before))["JAX_PLATFORMS"] == "cpu"
