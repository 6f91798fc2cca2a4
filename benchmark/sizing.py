"""Lower each cell's programs from shapes alone, for whatever devices are
handed in: a described (not attached) v5e in the compile test and when a
cell's fixed figures (pool tokens, batch) are found, the real chip
nowhere. Nothing runs here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from benchmark import checks, weights

HBM_BYTES = 16 * 1024 ** 3            # one v5e chip


def total_bytes(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def _on(sharding, tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def serve_programs(spec: dict, deployment: dict, device):
    """(decode step, {bucket: prefill}) lowered for one device."""
    from ray_tpu.models.paged_cache import (
        PagedConfig, init_paged_cache, make_paged_decode_step,
        make_paged_prefill)

    one = SingleDeviceSharding(device)
    cfg = checks.program_config(spec)
    slots, bs = deployment["num_slots"], deployment["kv_block_size"]
    page = PagedConfig(
        num_blocks=1 + -(-deployment["kv_pool_tokens"] // bs),
        block_size=bs, max_seq=deployment["max_seq"])
    params = _on(one, jax.eval_shape(
        weights.init_fn(spec), jax.eval_shape(lambda: jax.random.key(0))))
    cache = _on(one, jax.eval_shape(
        lambda: init_paged_cache(cfg, page, slots)))
    step = make_paged_decode_step(params, cfg, page)
    decode = step.jitted.lower(
        params, cache, _sds((slots, page.max_blocks_per_seq), jnp.int32, one),
        _sds((slots,), jnp.int32, one), _sds((slots,), jnp.bool_, one))
    prefill = make_paged_prefill(params, cfg, page)

    def bucket(pad_len):
        return prefill.jitted.lower(
            params, cache, _sds((page.max_blocks_per_seq,), jnp.int32, one),
            _sds((1, pad_len), jnp.int32, one), _sds((), jnp.int32, one),
            _sds((), jnp.int32, one), pad_len=pad_len)

    return decode, bucket


def train_mesh(devices, job: dict):
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(dp=1, fsdp=job.get("fsdp", 1),
                                tp=job.get("tp", 1)), devices=devices)


def train_setup(spec: dict, job: dict, mesh):
    """(state shapes with shardings, the jitted step, the rules)."""
    from ray_tpu.models import llama
    from ray_tpu.models.training import (OptimizerConfig, TrainState,
                                         make_train_step, state_shardings)
    from ray_tpu.parallel.sharding import FSDP_TP_RULES

    cfg = checks.program_config(spec)
    rules = FSDP_TP_RULES
    opt = OptimizerConfig(warmup_steps=1).make()
    init = weights.init_fn(spec)

    def build(key):
        params = init(key)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt.init(params))

    shape = jax.eval_shape(build, jax.eval_shape(lambda: jax.random.key(0)))
    shardings = state_shardings(shape, llama.param_logical_axes(cfg), mesh,
                                rules)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shape, shardings)
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg, rules),
                           opt, mesh, rules)
    return state, step, rules, opt, init


def train_program(spec: dict, job: dict, devices, batch: int):
    mesh = train_mesh(devices, job)
    with jax.sharding.set_mesh(mesh):
        state, step, *_ = train_setup(spec, job, mesh)
        tokens = _sds((batch, job["seq"]), jnp.int32,
                      NamedSharding(mesh, PartitionSpec()))
        return step.lower(state, {"tokens": tokens})
