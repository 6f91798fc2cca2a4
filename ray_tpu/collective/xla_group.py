"""XLA collective group — in-program ICI collectives.

The NCCL replacement (reference ``python/ray/util/collective/
collective_group/nccl_collective_group.py``), redesigned for XLA's
compilation model: a "group" is a device mesh axis owned by ONE
single-controller process, and each collective op is a tiny jitted program
whose collective rides ICI.

Convention: ops take a **stacked** array whose leading axis is the member
axis (length ``world_size``); the array is (re)sharded so member i's slab
lives on device i, the collective runs on-device over the mesh axis, and
the result comes back replicated (allreduce/allgather) or member-sharded
(reducescatter). This is the eager-op complement to writing ``psum`` /
``ppermute`` directly inside your own pjit programs — which remains the
idiomatic hot path (SURVEY.md §2.3: collectives compile into XLA programs).

Multi-host SPMD groups bootstrap a coordinator address via the internal KV
(exactly how the reference shares the NCCL uniqueid) and then use
``jax.distributed`` + the same jitted ops over the global mesh; the Train
worker group owns that wiring.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ray_tpu.collective.types import ReduceOp

_REDUCE_LAX = {
    ReduceOp.SUM: "psum",
    ReduceOp.MAX: "pmax",
    ReduceOp.MIN: "pmin",
}


class XlaGroup:
    backend_name = "xla"

    def __init__(self, world_size: int, rank: int = 0, group_name: str = "",
                 devices: Optional[list] = None, axis: str = "x"):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devices = devices if devices is not None else jax.devices()
        if world_size > len(devices):
            raise ValueError(
                f"world_size {world_size} exceeds {len(devices)} devices")
        self.world_size = world_size
        self.rank = rank
        self.group_name = group_name
        self.axis = axis
        self.mesh = Mesh(np.asarray(devices[:world_size]), (axis,))
        self._member_sharding = NamedSharding(self.mesh, P(axis))
        self._replicated = NamedSharding(self.mesh, P())
        self._fn_cache = {}  # per-instance: no cross-group lifetime pinning

    def _check(self, tensor):
        import numpy as _np

        tensor = _np.asarray(tensor) if not hasattr(tensor, "shape") else tensor
        if tensor.shape[0] != self.world_size:
            raise ValueError(
                f"leading (member) axis {tensor.shape[0]} != world_size "
                f"{self.world_size}")

    def _placed(self, tensor):
        import jax

        return jax.device_put(tensor, self._member_sharding)

    def _fn(self, kind: str, lax_name: str):
        cached = self._fn_cache.get((kind, lax_name))
        if cached is not None:
            return cached
        import jax
        from jax.sharding import PartitionSpec as P

        axis = self.axis

        if kind == "allreduce":
            def body(x):                       # per-device (1, ...)
                return getattr(jax.lax, lax_name)(x[0], axis)
            out_spec = P()
        elif kind == "reducescatter":
            def body(x):                       # per-device (1, W*c, ...)
                return jax.lax.psum_scatter(x[0], axis, tiled=True)
            out_spec = P(axis)
        else:
            raise AssertionError(kind)
        fn = jax.jit(self._shard_map(body, out_spec))
        self._fn_cache[(kind, lax_name)] = fn
        return fn

    def _shard_map(self, body, out_spec, check_rep=True):
        import jax
        from jax.sharding import PartitionSpec as P

        return jax.shard_map(body, mesh=self.mesh, in_specs=P(self.axis),
                             out_specs=out_spec, check_vma=check_rep)

    # ------------------------------------------------- quantized substrate
    def _quantization_block(self) -> int:
        from ray_tpu.common.config import GLOBAL_CONFIG

        return GLOBAL_CONFIG.get("quantized_collectives_block")

    def _use_quantized(self, tensor, op: ReduceOp) -> bool:
        """Quantized lowering applies to float SUM reductions only; every
        other (op, dtype) combination stays on the exact path, which also
        remains the default (RT_quantized_collectives=0) and is untouched
        by this routing — bit-identical results with the flag off."""
        import numpy as _np

        from ray_tpu.common.config import GLOBAL_CONFIG

        if not GLOBAL_CONFIG.get("quantized_collectives"):
            return False
        return (op is ReduceOp.SUM
                and _np.issubdtype(_np.asarray(tensor).dtype
                                   if not hasattr(tensor, "dtype")
                                   else tensor.dtype, _np.floating))

    def _quantized_fn(self, kind: str, block: int):
        """Two-phase quantized collective as ONE jitted shard_map program
        (EQuARX: quantize -> all_to_all codes -> dequant-sum -> requant ->
        all_gather -> dequant), built once per (kind, block) and cached —
        jit retraces per payload shape like every op here.
        ``check_rep=False``: all_to_all/all_gather outputs are replicated
        by construction but shard_map's rep tracking can't prove it.
        """
        cached = self._fn_cache.get((kind, block))
        if cached is not None:
            return cached
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ray_tpu.collective.quantization import (
            dequantize_blocks_jnp,
            quantize_blocks_jnp,
        )

        axis = self.axis
        W = self.world_size

        def _phase1(rows):
            """rows: (W, chunk) — this member's per-destination chunks.
            Returns this member's dequantized sum chunk (cpad,)."""
            chunk = rows.shape[1]
            cpad = -(-chunk // block) * block
            rows = jnp.pad(rows, ((0, 0), (0, cpad - chunk)))
            blocks = rows.reshape(W, cpad // block, block)
            codes, scale, lo = quantize_blocks_jnp(blocks)
            codes = jax.lax.all_to_all(codes, axis, 0, 0, tiled=True)
            scale = jax.lax.all_to_all(scale, axis, 0, 0, tiled=True)
            lo = jax.lax.all_to_all(lo, axis, 0, 0, tiled=True)
            deq = dequantize_blocks_jnp(codes, scale, lo, rows.dtype)
            return deq.sum(axis=0).reshape(-1)  # (cpad,)

        if kind == "allreduce_q":
            def body(x):                       # per-device (1, ...)
                v = x[0].reshape(-1)
                n = v.shape[0]
                chunk = -(-n // W)
                v = jnp.pad(v, (0, W * chunk - n))
                red = _phase1(v.reshape(W, chunk))       # my sum chunk
                cpad = red.shape[0]
                codes2, s2, l2 = quantize_blocks_jnp(
                    red.reshape(cpad // block, block))
                codes2 = jax.lax.all_gather(codes2, axis)  # (W, nb, block)
                s2 = jax.lax.all_gather(s2, axis)
                l2 = jax.lax.all_gather(l2, axis)
                full = dequantize_blocks_jnp(codes2, s2, l2, v.dtype)
                full = full.reshape(W, cpad)[:, :chunk].reshape(-1)[:n]
                return full.reshape(x.shape[1:])
            out_spec = P()
        elif kind == "reducescatter_q":
            def body(x):                       # per-device (1, W*c, ...)
                v = x[0]
                c = v.shape[0] // W
                rest = v.shape[1:]
                rows = v.reshape(W, -1)                   # (W, c*E)
                chunk = rows.shape[1]
                red = _phase1(rows)[:chunk]               # my sum chunk
                return red.reshape((c,) + rest)
            out_spec = P(axis)
        else:
            raise AssertionError(kind)
        fn = jax.jit(self._shard_map(body, out_spec, check_rep=False))
        self._fn_cache[(kind, block)] = fn
        return fn

    # ---------------------------------------------------------- collectives
    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM):
        """(W, ...) stacked → (...) reduced, replicated over the group."""
        self._check(tensor)
        lax_name = _REDUCE_LAX.get(op)
        if lax_name is None:
            raise ValueError(f"{op} unsupported by the xla backend")
        if self._use_quantized(tensor, op):
            return self._quantized_fn(
                "allreduce_q", self._quantization_block())(
                    self._placed(tensor))
        tensor = self._placed(tensor)
        return self._fn("allreduce", lax_name)(tensor)

    def reduce(self, tensor, dst_rank: int = 0, op: ReduceOp = ReduceOp.SUM):
        # Single-controller: result is replicated anyway.
        return self.allreduce(tensor, op)

    def broadcast(self, tensor, src_rank: int = 0):
        """Replicate member ``src_rank``'s slab over the group."""
        import jax

        self._check(tensor)
        tensor = self._placed(tensor)
        return jax.device_put(tensor[src_rank], self._replicated)

    def allgather(self, tensor) -> List:
        """(W, ...) stacked → list of W arrays, each replicated."""
        import jax

        self._check(tensor)
        tensor = self._placed(tensor)
        gathered = jax.device_put(tensor, self._replicated)
        return [gathered[i] for i in range(self.world_size)]

    def reducescatter(self, tensor, op: ReduceOp = ReduceOp.SUM):
        """(W, W·c, ...) stacked → (W, c, ...): member i gets the reduction
        of every member's i-th chunk (sharded, member i's chunk on device i).
        """
        tensor = np.asarray(tensor) if not hasattr(tensor, "shape") \
            else tensor
        self._check(tensor)
        if op is not ReduceOp.SUM:
            raise ValueError("xla reducescatter supports SUM only")
        if tensor.shape[1] % self.world_size:
            raise ValueError(
                f"axis-1 length {tensor.shape[1]} not divisible by "
                f"world size {self.world_size}")
        tensor = self._placed(tensor)
        if self._use_quantized(tensor, op):
            flat = self._quantized_fn(
                "reducescatter_q", self._quantization_block())(tensor)
        else:
            flat = self._fn("reducescatter", "psum")(tensor)  # (W*c, ...)
        return flat.reshape((self.world_size, -1) + tensor.shape[2:])

    def barrier(self):
        """Single-controller: drain the dispatch queue."""
        import jax

        jax.effects_barrier()

    def destroy(self):
        self._fn_cache.clear()
