"""Lower each cell's programs from shapes alone, for whatever devices are
handed in: a described (not attached) v5e in the compile test and when a
cell's fixed figures (pool tokens, batch) are found, the real chip
nowhere. Nothing runs here. The programs themselves are the block's:
its adapter lowers them (``lower_serve_programs``, ``train_setup``).
"""

from __future__ import annotations

import collections
import re

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from benchmark import model_spec, trace_reduce

HBM_BYTES = 16 * 1024 ** 3            # one v5e chip


def total_bytes(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def kernel_calls(text: str) -> dict:
    """{kernel: its custom calls in a compiled program's text}, by the
    instruction name the program gives its ``pallas_call`` (the compiler
    numbers the instances: ``%paged_decode_attention.5``)."""
    ops = (trace_reduce.parse_op(line.strip()) for line in text.splitlines()
           if "tpu_custom_call" in line)
    return collections.Counter(
        re.sub(r"\.\d+$", "", name.split("%")[-1])
        for name, opcode, _ in ops if opcode == "custom-call:tpu_custom_call")


def on(sharding, tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def serve_programs(spec: dict, deployment: dict, device):
    """(decode step, bucket -> prefill) lowered for one device."""
    return model_spec.adapter(spec).lower_serve_programs(spec, deployment,
                                                         device)


def train_mesh(devices, job: dict):
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(dp=1, fsdp=job.get("fsdp", 1),
                                tp=job.get("tp", 1)), devices=devices)


def train_program(spec: dict, job: dict, devices, batch: int):
    mesh = train_mesh(devices, job)
    with jax.sharding.set_mesh(mesh):
        state, step, *_ = model_spec.adapter(spec).train_setup(spec, job,
                                                               mesh)
        tokens = sds((batch, job["seq"]), jnp.int32,
                     NamedSharding(mesh, PartitionSpec()))
        return step.lower(state, {"tokens": tokens})
