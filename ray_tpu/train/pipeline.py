"""MPMD pipeline-parallel training over resident stage actors.

The model is split into stages (:class:`StageSpec`); each stage runs as a
resident actor executing a 1F1B microbatch schedule (warmup forwards,
steady-state one-forward-one-backward interleave, drain backwards) over
preallocated :class:`~ray_tpu.graph.channels.ShmChannel` hops — the same
depth-1 mutable-shm transport the compiled actor graphs ride
(``graph/compiled.py``), so per-microbatch cost is one memcpy + condvar
wake per hop with **no per-microbatch RPC or driver involvement**.  The
driver only feeds microbatches into the head channel and reads one
metrics record per *step* from the tail.

Topology per data-parallel replica ``r`` (S stages, M microbatches)::

    driver ──x──▶ stage 0 ──act──▶ stage 1 ─ … ─▶ stage S-1 ──res──▶ driver
    driver ──y────────────────────────────────────▶ stage S-1
              stage 0 ◀──grad── stage 1 ◀─ … ─◀ stage S-1

Backward uses full recompute (``jax.vjp`` of the stage's forward at the
stashed input), and the last stage fuses loss + gradient into one jitted
``value_and_grad`` at its forward slot, so warmup for stage ``i`` is
``min(S-1-i, M)`` and the schedule is deadlock-free on depth-1 channels.
Gradients accumulate across microbatches; the data-parallel allreduce (or
ZeRO reducescatter/allgather via
:class:`~ray_tpu.train.collectives.ZeroShardedOptimizer`) folds into the
stage loop at the step boundary — it rides the quantized collective wire
when ``RT_quantized_collectives`` is on.

This module is deliberately independent of :class:`JaxTrainer`: anything
that wants resident stage actors streaming microbatches (e.g. a Podracer
style RL learner feeding trajectories) can drive a
:class:`PipelineRunner` directly.
"""

from __future__ import annotations

import collections
import dataclasses
import uuid
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

from ray_tpu.graph.compiled import PipelineStageError

_LOOP_IO_TIMEOUT_S = 600.0  # stage-loop channel ops; driver watches refs


@dataclasses.dataclass
class StageSpec:
    """One pipeline stage: ``init(rng) -> params``,
    ``apply(params, x) -> y``.  ``apply`` must be jit-traceable; backward
    is derived from it via ``jax.vjp`` (full recompute)."""

    init: Callable[..., Any]
    apply: Callable[[Any, Any], Any]
    name: str = ""


@dataclasses.dataclass
class PipelineSpec:
    """Declarative pipeline: stages + schedule + optimizer.

    ``loss(y_pred, y) -> scalar`` is fused with the last stage's forward.
    ``data_parallel`` replicates the whole pipeline R times with gradient
    allreduce across replicas folded into each stage's step boundary;
    ``zero_sharded_state`` switches that allreduce to the ZeRO
    reducescatter → shard-update → allgather form (optimizer state sharded
    1/R per replica).  ``num_steps``/``data_fn`` are consumed by
    ``JaxTrainer.fit`` only — ``PipelineRunner`` users drive ``step()``
    themselves.
    """

    stages: Sequence[StageSpec]
    loss: Callable[[Any, Any], Any]
    num_microbatches: int = 4
    optimizer: str = "sgd"
    learning_rate: float = 0.01
    data_parallel: int = 1
    zero_sharded_state: bool = False
    channel_capacity: int = 4 * 1024 * 1024
    seed: int = 0
    num_steps: int = 1
    data_fn: Optional[Callable[[int], Any]] = None

    def __post_init__(self):
        if not self.stages:
            raise ValueError("PipelineSpec needs at least one stage")
        if self.num_microbatches < 1:
            raise ValueError("num_microbatches must be >= 1")
        if self.data_parallel < 1:
            raise ValueError("data_parallel must be >= 1")
        if self.zero_sharded_state and self.data_parallel < 2:
            raise ValueError(
                "zero_sharded_state shards optimizer state across "
                "data-parallel replicas; it needs data_parallel >= 2")


class _CleanStop(Exception):
    """Input channel closed at a step boundary: normal termination."""


def _host(value):
    """Pytree of device arrays -> pytree of host numpy (wire format)."""
    import jax

    return jax.tree_util.tree_map(np.asarray, jax.device_get(value))


class _PipelineStageActor:
    """Resident stage: builds its jitted programs once, then runs the
    1F1B loop until its input channel closes (clean stop cascades head to
    tail through channel closure)."""

    def __init__(self, stage_blob: bytes, index: int, n_stages: int,
                 num_microbatches: int, seed: int, optimizer: str,
                 learning_rate: float, dp_spec=None):
        import cloudpickle

        fns = cloudpickle.loads(stage_blob)
        self._init_fn = fns["init"]
        self._apply_fn = fns["apply"]
        self._loss_fn = fns.get("loss")
        self._index = index
        self._n_stages = n_stages
        self._M = num_microbatches
        self._seed = seed
        self._opt_kind = optimizer
        self._lr = learning_rate
        self._dp_spec = dp_spec  # (tag, rank, world, zero) | None
        self._is_last = index == n_stages - 1

    # ------------------------------------------------------------- programs
    def _build_fns(self):
        """One jit scope per program, built ONCE per actor lifetime — the
        loop replays them (stable shapes → no retrace per microbatch)."""
        import jax

        apply_fn = self._apply_fn
        fwd = jax.jit(apply_fn)

        def _bwd(p, x, g):
            _, vjp = jax.vjp(apply_fn, p, x)
            return vjp(g)  # (grad_params, grad_x)

        bwd = jax.jit(_bwd)
        fused = None
        if self._is_last:
            loss_fn = self._loss_fn

            def _loss(p, x, y):
                return loss_fn(apply_fn(p, x), y)

            fused = jax.jit(jax.value_and_grad(_loss, argnums=(0, 1)))
        return fwd, bwd, fused

    # ------------------------------------------------------------ exec loop
    def run_pipeline(self, in_ch, out_ch, grad_in_ch, grad_out_ch,
                     label_ch, result_ch):
        """Run steps until ``in_ch`` closes; returns final host params."""
        import jax
        from jax.flatten_util import ravel_pytree

        from ray_tpu.train.collectives import (
            FlatOptimizer,
            ZeroShardedOptimizer,
        )

        params = _host(self._init_fn(
            jax.random.PRNGKey(self._seed + self._index)))
        fwd, bwd, fused = self._build_fns()
        opt = FlatOptimizer(kind=self._opt_kind, lr=self._lr)
        opt_state = None
        dp_group = zero = None
        if self._dp_spec is not None:
            tag, rank, world, use_zero = self._dp_spec
            from ray_tpu import collective as _coll

            # every replica's stage-i loop starts concurrently → the KV
            # rendezvous for this per-stage group completes
            group_name = f"{tag}:dp:{self._index}"
            _coll.init_collective_group(world, rank, backend="kv",
                                        group_name=group_name)
            dp_group = _coll.get_group_handle(group_name)
            if use_zero:
                zero = ZeroShardedOptimizer(dp_group, opt)

        out_chans = [c for c in (out_ch, grad_out_ch, result_ch)
                     if c is not None]
        step = 0
        try:
            while True:
                try:
                    grads, loss = self._one_step(params, fwd, bwd, fused,
                                                 in_ch, out_ch, grad_in_ch,
                                                 grad_out_ch, label_ch)
                except _CleanStop:
                    break
                pflat, unravel = ravel_pytree(params)
                pflat = np.asarray(pflat)
                gflat = np.asarray(ravel_pytree(grads)[0])
                if zero is not None:
                    new_flat = zero.step(pflat, gflat, average=True)
                else:
                    if dp_group is not None:
                        gflat = np.asarray(
                            dp_group.allreduce(gflat)) / dp_group.world_size
                    if opt_state is None:
                        opt_state = opt.init_state(pflat.size, pflat.dtype)
                    new_flat = opt.update(pflat, gflat, opt_state)
                params = _host(unravel(new_flat))
                step += 1
                if result_ch is not None:
                    result_ch.write({"step": step, "loss": loss},
                                    timeout_s=_LOOP_IO_TIMEOUT_S)
        except BaseException:
            # error stop: close OUR output ends first so blocked neighbors
            # wake with ChannelClosed (cascade) instead of riding out
            # their timeouts, then let the loop ref carry the real error
            for c in out_chans:
                c.close()
            raise
        for c in out_chans:  # clean stop: cascade closure downstream
            c.close()
        return params

    def _one_step(self, params, fwd, bwd, fused, in_ch, out_ch, grad_in_ch,
                  grad_out_ch, label_ch):
        """One 1F1B step over M microbatches; returns (mean grads pytree,
        mean loss or None).  ChannelClosed on the FIRST read of the step
        is a clean stop; anywhere else it propagates as an error."""
        import jax

        from ray_tpu.graph.channels import ChannelClosed

        M = self._M
        warmup = min(self._n_stages - 1 - self._index, M)
        stash = collections.deque()
        acc = [None]
        loss_sum = [0.0]
        first = [True]

        def add(g):
            acc[0] = g if acc[0] is None else jax.tree_util.tree_map(
                lambda a, b: a + b, acc[0], g)

        def forward():
            try:
                x = in_ch.read(timeout_s=_LOOP_IO_TIMEOUT_S)
            except ChannelClosed:
                if first[0]:
                    raise _CleanStop from None
                raise
            first[0] = False
            if self._is_last:
                y = label_ch.read(timeout_s=_LOOP_IO_TIMEOUT_S)
                loss, (gp, gx) = fused(params, x, y)
                loss_sum[0] += float(loss)
                add(gp)
                if grad_out_ch is not None:
                    grad_out_ch.write(_host(gx),
                                      timeout_s=_LOOP_IO_TIMEOUT_S)
            else:
                yv = fwd(params, x)
                stash.append(x)
                out_ch.write(_host(yv), timeout_s=_LOOP_IO_TIMEOUT_S)

        def backward():
            if self._is_last:
                return  # fused into the forward slot
            g = grad_in_ch.read(timeout_s=_LOOP_IO_TIMEOUT_S)
            gp, gx = bwd(params, stash.popleft(), g)
            add(gp)
            if grad_out_ch is not None:
                grad_out_ch.write(_host(gx), timeout_s=_LOOP_IO_TIMEOUT_S)

        for _ in range(warmup):
            forward()
        for _ in range(M - warmup):
            forward()
            backward()
        for _ in range(warmup):
            backward()

        import jax as _jax  # grads averaged over microbatches

        grads = _jax.tree_util.tree_map(lambda a: np.asarray(a) / M, acc[0])
        loss = loss_sum[0] / M if self._is_last else None
        return grads, loss


class PipelineRunner:
    """Driver handle: creates channels + stage actors, starts the exec
    loops, then ``step(xs, ys)`` streams one step's microbatches and
    returns the step metrics.  ``finish()`` closes the head channels
    (clean-stop cascade) and returns the final stage params.

    A stage actor killed mid-pipeline surfaces as
    :class:`~ray_tpu.graph.compiled.PipelineStageError` from ``step()``
    within the caller's deadline — channel waits run in short slices with
    the stage loop refs polled between slices, exactly like the compiled
    DAG's ``execute()``."""

    def __init__(self, spec: PipelineSpec, actor_options: Optional[dict] = None):
        import cloudpickle

        import ray_tpu
        from ray_tpu.graph.channels import ShmChannel

        self.spec = spec
        S = len(spec.stages)
        R = spec.data_parallel
        tag = uuid.uuid4().hex[:10]
        self._tag = tag
        cap = spec.channel_capacity
        self._channels: List[ShmChannel] = []

        def make(name):
            ch = ShmChannel(f"/rtpp_{tag}_{name}", capacity=cap,
                            num_readers=1)
            ch._handle()  # create the segment before any actor opens it
            self._channels.append(ch)
            return ch

        self._x = [make(f"x{r}") for r in range(R)]
        self._y = [make(f"y{r}") for r in range(R)]
        self._res = [make(f"res{r}") for r in range(R)]
        acts = [[make(f"a{r}_{i}") for i in range(S - 1)] for r in range(R)]
        grads = [[make(f"g{r}_{i}") for i in range(S - 1)] for r in range(R)]

        self._actors = []
        self._loop_refs = []
        remote_cls = ray_tpu.remote(_PipelineStageActor)
        opts = dict(actor_options or {})
        opts.setdefault("num_cpus", 0)
        for r in range(R):
            for i, stage in enumerate(spec.stages):
                blob = cloudpickle.dumps(
                    {"init": stage.init, "apply": stage.apply,
                     "loss": spec.loss if i == S - 1 else None})
                dp_spec = (tag, r, R, spec.zero_sharded_state) \
                    if R > 1 else None
                handle = remote_cls.options(**opts).remote(
                    blob, i, S, spec.num_microbatches, spec.seed,
                    spec.optimizer, spec.learning_rate, dp_spec)
                self._actors.append(handle)
                in_ch = self._x[r] if i == 0 else acts[r][i - 1]
                out_ch = acts[r][i] if i < S - 1 else None
                grad_in = grads[r][i] if i < S - 1 else None
                grad_out = grads[r][i - 1] if i > 0 else None
                label = self._y[r] if i == S - 1 else None
                res = self._res[r] if i == S - 1 else None
                self._loop_refs.append(handle.run_pipeline.remote(
                    in_ch, out_ch, grad_in, grad_out, label, res))
        self._step = 0
        self._done = False

    # ----------------------------------------------------- failure watching
    def _check_stage_loops(self):
        if not self._loop_refs:
            return
        import ray_tpu

        done, _ = ray_tpu.wait(self._loop_refs,
                               num_returns=len(self._loop_refs), timeout=0)
        for ref in done:
            try:
                ray_tpu.get(ref)
            except Exception as e:  # noqa: BLE001 — actor death/loop error
                raise PipelineStageError(
                    f"pipeline stage exec loop failed: "
                    f"{type(e).__name__}: {e}") from e

    def _watched(self, op, timeout_s: float):
        """Run a channel read/write in short slices, polling the stage
        loop refs between slices; a dead stage raises typed within the
        deadline instead of hanging the channel wait."""
        from ray_tpu.common.retry import Deadline

        deadline = Deadline(timeout_s)
        while True:
            try:
                return op(deadline.remaining(cap=0.2) or 0.0)
            except TimeoutError:
                if deadline.expired():
                    raise
                self._check_stage_loops()

    # ----------------------------------------------------------------- step
    def step(self, xs: Sequence, ys: Sequence,
             timeout_s: float = 120.0) -> dict:
        """Feed one step: ``xs``/``ys`` hold ``num_microbatches *
        data_parallel`` microbatch arrays (replica-major: replica r gets
        ``xs[r*M:(r+1)*M]``).  Returns ``{"step", "loss"}`` with the loss
        averaged across replicas."""
        if self._done:
            raise RuntimeError("pipeline already finished")
        M = self.spec.num_microbatches
        R = self.spec.data_parallel
        if len(xs) != M * R or len(ys) != M * R:
            raise ValueError(
                f"need {M * R} microbatches (M={M} x R={R}), got "
                f"{len(xs)}/{len(ys)}")
        try:
            for m in range(M):
                for r in range(R):
                    x, y = np.asarray(xs[r * M + m]), np.asarray(ys[r * M + m])
                    self._watched(
                        lambda t, c=self._x[r], v=x: c.write(v, timeout_s=t),
                        timeout_s)
                    self._watched(
                        lambda t, c=self._y[r], v=y: c.write(v, timeout_s=t),
                        timeout_s)
            losses = []
            for r in range(R):
                rec = self._watched(
                    lambda t, c=self._res[r]: c.read(timeout_s=t), timeout_s)
                losses.append(rec["loss"])
        except PipelineStageError:
            self.shutdown()
            raise
        self._step += 1
        return {"step": self._step, "loss": float(np.mean(losses))}

    # --------------------------------------------------------------- finish
    def finish(self, timeout_s: float = 120.0) -> List[Any]:
        """Close the head channels (clean-stop cascades tail-ward), join
        the stage loops, and return replica 0's per-stage final params."""
        import ray_tpu

        if self._done:
            raise RuntimeError("pipeline already finished")
        self._done = True
        for ch in self._x + self._y:
            ch.close()
        try:
            all_params = ray_tpu.get(self._loop_refs)
        except Exception as e:  # noqa: BLE001 — a stage died during drain
            self.shutdown()
            raise PipelineStageError(
                f"pipeline stage failed during drain: "
                f"{type(e).__name__}: {e}") from e
        S = len(self.spec.stages)
        return list(all_params[:S])  # replica 0 is the first S loop refs

    def shutdown(self):
        """Idempotent teardown: close + unlink channels, kill actors."""
        import ray_tpu

        self._done = True
        for ch in self._channels:
            ch.close()
            ch.unlink()
        self._channels = []
        for handle in self._actors:
            try:
                ray_tpu.kill(handle)
            except Exception:  # noqa: BLE001 — already dead
                pass
        self._actors = []
        self._loop_refs = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False
