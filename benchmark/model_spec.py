"""A configuration and what belongs to it, found by name.

``configs/<config>.json`` names its block (``"architecture"``), its plain
reference (``"reference"``, a path from the root of the repo) and, where
the shared ``limits.json`` does not hold for it, its own limits
(``"limits"``). The block's adapter is ``architectures/<architecture>.py``:
everything the harness knows about a block sits there (the contract is
the table in ``benchmark/README.md``), and nothing here or in the other
modules names one. A piece that is missing fails with the path that was
looked for.

Pure Python (no jax): the driver process reads it, the worker that holds
the chip reads it, the tests read it. An adapter's import imports no jax
either; a reference's does, so ``reference`` is for a process that holds
the device.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


@functools.lru_cache(maxsize=None)
def load_module(path: str):
    """The Python file at ``path`` as a module, loaded once: how every
    piece that is found by name (an adapter, a reference, a per-layer
    reader) comes in."""
    stem = os.path.splitext(os.path.basename(path))[0]
    found = importlib.util.spec_from_file_location(
        "benchmark_file_" + "".join(c if c.isalnum() else "_"
                                    for c in stem), path)
    mod = importlib.util.module_from_spec(found)
    found.loader.exec_module(mod)
    return mod


def adapter_path(spec: dict, root: str = HERE) -> str:
    return os.path.join(root, "architectures", f"{spec['architecture']}.py")


def _from_repo(spec: dict, key: str, root: str) -> str:
    return os.path.join(os.path.dirname(root), spec[key])


def adapter(spec: dict, root: str = HERE):
    """The module ``architectures/<architecture>.py`` of this
    configuration's block."""
    return load_module(adapter_path(spec, root))


def reference(spec: dict, root: str = HERE):
    """The configuration's plain reference: ``logits(params, tokens,
    spec, rows, quant=None)``, ``last_block_loss_and_grads``,
    ``rel_err``. Imports jax."""
    return load_module(_from_repo(spec, "reference", root))


def limits_path(spec: dict, root: str = HERE) -> str:
    """The configuration's own limits where it names a file, else
    ``limits.json``."""
    return (_from_repo(spec, "limits", root) if "limits" in spec
            else os.path.join(root, "limits.json"))


def limits(spec: dict, root: str = HERE) -> dict:
    """{name: {"limit": ..., the readings it was set from}}."""
    with open(limits_path(spec, root)) as f:
        return json.load(f)["limits"]


def load_config(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "configs", f"{name}.json")) as f:
        spec = json.load(f)
    for key in ("architecture", "reference"):
        if key not in spec:
            raise SystemExit(f"config {name!r} names no {key!r}")
    for what, path in (("architecture", adapter_path(spec, root)),
                       ("reference", _from_repo(spec, "reference", root)),
                       ("limits", limits_path(spec, root))):
        if not os.path.isfile(path):
            raise SystemExit(f"config {name!r}: its {what} is not there: "
                             f"no file {path}")
    if spec.get("torch_dtype") != "bfloat16":
        raise SystemExit(f"config {name!r}: only bfloat16 is served")
    adapter(spec, root).check_config(spec)
    return spec


def _of_adapter(name: str):
    def call(spec: dict, *args, **kwargs):
        return getattr(adapter(spec), name)(spec, *args, **kwargs)

    call.__name__ = name
    call.__doc__ = f"``{name}`` of the configuration's adapter."
    return call


# the counts every adapter gives, by the configuration
num_params = _of_adapter("num_params")
matrix_params = _of_adapter("matrix_params")
train_flops_per_token = _of_adapter("train_flops_per_token")
kv_bytes_per_token = _of_adapter("kv_bytes_per_token")
kernel_counts = _of_adapter("kernel_counts")
# and three of the two configurations that are there, under the names
# they had before the adapters (an adapter without them: AttributeError)
flash_flops = _of_adapter("flash_flops")
paged_decode_bytes = _of_adapter("paged_decode_bytes")
program_kwargs = _of_adapter("program_kwargs")
