"""TPU metadata autodetection.

Reference: ``python/ray/_private/accelerators/tpu.py`` — chips detected via
``TPU_ACCELERATOR_TYPE``/GCE metadata (``:16-30``), pod worker counts from
the accelerator type (``:313``), slice name + worker index advertised as
scheduling labels (``:338-374``). Here the same environment surface feeds
first-class ``TPU`` resources and ``rt.io/tpu-*`` labels automatically, so
``SLICE_PACK`` placement works without hand-set ``num_tpus``.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Optional

# chips per HOST by accelerator generation (public TPU VM shapes: v2/v3
# are 4-chip half-boards per VM, v4/v5p 4, v5e/v6e up to 8 for the
# single-host shapes and 4 for pod slices).
_DEFAULT_CHIPS_PER_HOST = 4
_SINGLE_HOST_V5E = {"v5litepod-1": 1, "v5litepod-4": 4, "v5litepod-8": 8,
                    "v6e-1": 1, "v6e-4": 4, "v6e-8": 8}


def _chips_from_accelerator_type(acc: str) -> Optional[int]:
    """'v5litepod-16' → chips on THIS host (not the whole slice)."""
    acc = acc.strip().lower()
    if not acc:
        return None
    if acc in _SINGLE_HOST_V5E:
        return _SINGLE_HOST_V5E[acc]
    try:
        total = int(acc.rsplit("-", 1)[1])
    except (IndexError, ValueError):
        return None
    return min(total, _DEFAULT_CHIPS_PER_HOST)


def _count_chip_nodes(dev_root: str) -> int:
    """Chips this host exposes as device files: ``accelN`` nodes, or
    numbered vfio groups. ``/dev/vfio/vfio`` is the control node of the
    vfio driver, not a chip."""
    try:
        accel = [n for n in os.listdir(dev_root)
                 if re.fullmatch(r"accel\d+", n)]
    except OSError:
        accel = []
    if accel:
        return len(accel)
    try:
        return sum(n.isdigit()
                   for n in os.listdir(os.path.join(dev_root, "vfio")))
    except OSError:
        return 0


def detect(dev_root: str = "/dev") -> Dict[str, object]:
    """Local TPU discovery from the environment and the device files.

    Returns {"chips": float, "topology": str|None, "slice_name": str|None,
    "worker_id": int|None}. Never touches jax (that would claim the
    chips before the worker that should own them)."""
    chips: Optional[float] = None
    topology = (os.environ.get("TPU_ACCELERATOR_TYPE")
                or os.environ.get("ACCELERATOR_TYPE") or None)

    if os.environ.get("TPU_VISIBLE_CHIPS"):
        chips = float(len(os.environ["TPU_VISIBLE_CHIPS"].split(",")))
    if chips is None:
        # what this host can open beats what the slice is called: a
        # container handed one chip of a v5litepod-4 has one chip
        chips = float(_count_chip_nodes(dev_root)) or None
    if chips is None and topology:
        got = _chips_from_accelerator_type(topology)
        if got is not None:
            chips = float(got)
    worker_id = None
    if os.environ.get("TPU_WORKER_ID"):
        try:
            worker_id = int(os.environ["TPU_WORKER_ID"])
        except ValueError:
            pass
    slice_name = (os.environ.get("TPU_NAME")
                  or os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")[0]
                  or None)
    return {"chips": float(chips or 0.0), "topology": topology,
            "slice_name": slice_name, "worker_id": worker_id}


# ------------------------------------------------- one process for each chip
# A chip belongs to one process at a time, and JAX takes every chip it can
# see the first time a process uses it. So: a process that holds no TPU
# lease runs JAX on the CPU; a worker that is granted chips sees exactly
# those chips and the platforms its environment named — decided before JAX
# is first imported in that process.

# per-process chip grid by chip count (v5e host shapes: 1, 1x2, 2x2, 2x4)
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}
_TPU_PROCESS_PORT_BASE = 8476

# While the pin is on: what JAX_PLATFORMS was before it (None = unset).
_UNPINNED = object()
_platforms_before_pin = _UNPINNED
# the chips this process was last granted by its raylet (None = no lease)
granted_chips: Optional[List[int]] = None


def leaseless_env(env: dict) -> dict:
    """Environment for a child that holds no TPU lease and never will (a
    job or client-session driver): JAX on the CPU."""
    env["JAX_PLATFORMS"] = "cpu"
    return env


def pin_cpu_until_granted(environ=os.environ) -> None:
    """First thing a worker process does. Warm-pool and factory-forked
    workers exist before any lease does, so the pin is one that
    :func:`grant_chips` can lift."""
    global _platforms_before_pin
    _platforms_before_pin = environ.get("JAX_PLATFORMS") or None
    environ["JAX_PLATFORMS"] = "cpu"


def grant_chips(chips: List[int], environ=os.environ, modules=sys.modules,
                dev_root: str = "/dev") -> None:
    """A lease with ``chips`` landed on this worker: make exactly those
    chips visible and lift the CPU pin. Raises if JAX was already
    imported under the pin — that process can never see the chips, and
    carrying on would run the lease's work on the CPU under a TPU's
    name; the raylet retires the worker and takes a fresh one."""
    global _platforms_before_pin, granted_chips
    granted_chips = list(chips)
    environ["TPU_VISIBLE_CHIPS"] = ",".join(str(i) for i in chips)
    if not chips:
        return
    if len(chips) == _count_chip_nodes(dev_root):
        # the whole host: nothing to hide, the runtime's own defaults
        # (and whatever topology the host's environment describes) stand
        del environ["TPU_VISIBLE_CHIPS"]
    else:
        # several processes share the host, each its own one-process
        # "slice" over its chips with a runtime port of its own. Both
        # spellings of the bounds: a host that presets the older names
        # must not be left describing the whole board to this process.
        bounds = _CHIP_BOUNDS.get(len(chips))
        if bounds is not None:
            environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
            environ["TPU_CHIPS_PER_HOST_BOUNDS"] = bounds
        port = _TPU_PROCESS_PORT_BASE + min(chips)
        environ["TPU_PROCESS_BOUNDS"] = "1,1,1"
        environ["TPU_HOST_BOUNDS"] = "1,1,1"
        environ["TPU_PROCESS_ADDRESSES"] = f"localhost:{port}"
        environ["TPU_PROCESS_PORT"] = str(port)
        environ["CLOUD_TPU_TASK_ID"] = "0"
    if _platforms_before_pin is _UNPINNED or _platforms_before_pin == "cpu":
        return      # not pinned by the rule / a CPU-only deployment
    if "jax" in modules:
        raise RuntimeError(
            "this worker imported jax on the CPU before it was granted "
            f"TPU chips {chips}; it cannot open them — the lease needs a "
            "fresh worker")
    if _platforms_before_pin is None:
        del environ["JAX_PLATFORMS"]
    else:
        environ["JAX_PLATFORMS"] = _platforms_before_pin
    _platforms_before_pin = _UNPINNED
    from ray_tpu.common.compile_cache import use_compile_cache

    use_compile_cache(environ, modules)
