"""Kernel-level profiling hooks (reference: Ray exposes torch/nsight
profilers via runtime hooks; the TPU-native equivalent is the XLA/jax
profiler, whose traces open in TensorBoard/Perfetto and show per-kernel
MXU/HBM utilization).

Four entry points:

- :func:`profile` — context manager around a training/serving region;
  writes an XLA profiler trace directory (the evidence artifact for
  perf work, e.g. the MFU investigations in PERF_PLAN.md).
- :func:`annotate` — named sub-region inside a profile (TraceAnnotation)
  so framework phases (data load, step, collective) are visible between
  kernels.
- :class:`Phases` — the named phases of one loop: each is a
  TraceAnnotation on the profiler's clock AND a row of always-on
  counters (count, wall seconds and, sampled, the thread's CPU
  seconds), so the same names read the same work with and without a
  capture.

- :func:`part` — the named part of a block that a device operation
  belongs to (``jax.named_scope`` under ONE vocabulary, :data:`PARTS`):
  metadata of the compiled instructions, read from a capture by
  ``benchmark/layer_metrics/_dev_ms_by_part.py``.

``profile`` and ``annotate`` degrade to no-ops when jax's profiler is
unavailable (e.g. a worker without jax initialized), so library code
can call them unconditionally. This module imports without jax.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import time
from typing import Dict, Iterator, List, Optional

logger = logging.getLogger(__name__)


@contextlib.contextmanager
def profile(logdir: str) -> Iterator[str]:
    """Capture an XLA profiler trace of the enclosed region into
    ``logdir`` (one subdirectory per capture). Returns the logdir so
    callers can print/record the artifact path."""
    os.makedirs(logdir, exist_ok=True)
    try:
        import jax

        jax.profiler.start_trace(logdir,
                                 create_perfetto_trace=False)
        started = True
    except Exception as e:  # noqa: BLE001 — no device/profiler: no-op
        logger.debug("profiler unavailable: %s", e)
        started = False
    t0 = time.monotonic()
    try:
        yield logdir
    finally:
        if started:
            try:
                import jax

                jax.profiler.stop_trace()
                logger.info("profile trace (%.1fs) written to %s",
                            time.monotonic() - t0, logdir)
            except Exception as e:  # noqa: BLE001
                logger.warning("stop_trace failed: %s", e)


class _NoAnnotation(contextlib.nullcontext):
    def __init__(self, name: str, **attrs):
        super().__init__()


@functools.lru_cache(maxsize=None)
def _annotations():
    """(TraceAnnotation, StepTraceAnnotation), looked up once."""
    try:
        import jax

        return jax.profiler.TraceAnnotation, jax.profiler.StepTraceAnnotation
    except Exception:  # noqa: BLE001 — no jax: spans are no-ops
        return _NoAnnotation, _NoAnnotation


def annotate(name: str, **attrs):
    """Named region inside a capture (shows as a host-side bar above the
    device kernels it launched); ``attrs`` become the event's stats."""
    return _annotations()[0](name, **attrs)


# The parts of a block, for the DEVICE's operations: every operation of
# the decode step, a prefill and the train step is traced under one of
# these names, which becomes a component of its ``op_name`` in the
# compiled program and of the ``tf_op`` stat of its event in a capture.
# The innermost name wins, so a kernel inside ``expert_layer`` reads as
# the kernel. PERF.md section 3 lists where each is opened and which
# metric reads it; the benchmark's reader holds a copy that a test holds
# equal to this one.
PARTS = (
    # the block's own arithmetic
    "embed", "attn_proj", "kv_store", "attention", "mlp", "router",
    "expert_dispatch", "expert_combine", "head", "loss", "optimizer",
    # what a model adds
    "expert_layer", "shared_expert", "attn_gate", "mla_expand",
    "mla_absorb",
    # the kernels, each under its custom call's own name
    "paged_decode_attention", "paged_hybrid_decode_full",
    "paged_hybrid_decode_window", "paged_mla_decode",
    "grouped_expert_matmul", "grouped_expert_matmul_prefill",
    "flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv",
)
# What a state-space layer and a latent expert layer add (PR 39,
# ``models/nemotron_h.py``): the in and out projections of the mixer, its
# causal convolution, the chunked scan of a prefill, the decode step's
# state update (the kernel ``ssm_decode_update`` and its glue), the gate
# and grouped norm, and the projections into and out of the experts'
# latent width. The same vocabulary, in a tuple of its own as a model
# that adds parts has a file of its own on the reader's side: the
# reader's vocabulary is the union of
# ``benchmark/layer_metrics/parts/*.json`` (``base.json`` is ``PARTS``,
# ``state_space.json`` this tuple; a test there holds each pair equal).
SSM_PARTS = (
    "ssm_proj", "ssm_conv", "ssm_scan", "ssm_update", "ssm_gate_norm",
    "latent_proj",
)


# What a model that generates by diffusion over blocks adds (PR 48,
# ``models/sdar.py``): the RMSNorm over a head's width on every query
# and key head, and the deciding program's arithmetic (the softmax of a
# block's logits, the pick, the ranking of the undecided positions). The
# reader's side is ``benchmark/layer_metrics/parts/sdar.json``.
BLOCK_PARTS = ("qk_norm", "block_decide")
# What a model whose layers run several times a token adds (PR 52,
# ``models/ouro.py``): the two norms AFTER a block's sublayers, and what
# stands between two passes (the norm, the exit gate, the exit
# distribution and the choice of the pass whose state the head reads).
# The reader's side is ``benchmark/layer_metrics/parts/ouro.json``.
LOOP_PARTS = ("post_norm", "exit_gate")
_VOCABULARY = PARTS + SSM_PARTS + BLOCK_PARTS + LOOP_PARTS


def part(name: str):
    """``jax.named_scope(name)`` for a ``name`` of :data:`PARTS` (or of
    :data:`SSM_PARTS`, :data:`BLOCK_PARTS` or :data:`LOOP_PARTS`): what is traced inside
    belongs to that part of the block. Checked while tracing; nothing
    runs for it on the device or in a loop's turn."""
    if name not in _VOCABULARY:
        raise ValueError(f"{name!r} is no part of a block: one of "
                         f"{_VOCABULARY}")
    import jax

    return jax.named_scope(name)


class _Phase:
    """One entry into a phase: the annotation, and on exit the row."""

    __slots__ = ("_owner", "_name", "_span", "_t0", "_c0", "_children",
                 "_cpu")

    def __init__(self, owner: "Phases", name: str, span, cpu: bool):
        self._owner, self._name, self._span = owner, name, span
        self._cpu = cpu

    def __enter__(self):
        self._children = [0.0, 0.0]
        self._owner._stack.append(self._children)
        self._span.__enter__()
        self._t0 = time.monotonic()
        self._c0 = time.thread_time() if self._cpu else 0.0
        return self

    def __exit__(self, *exc):
        wall = time.monotonic() - self._t0
        cpu = time.thread_time() - self._c0 if self._cpu else 0.0
        self._span.__exit__(*exc)
        stack = self._owner._stack
        stack.pop()
        if stack:
            stack[-1][0] += wall
            stack[-1][1] += cpu
        else:
            self._owner._cpu = True     # the turn is over
        row = self._owner._rows.get(self._name)
        if row is None:
            row = self._owner._rows[self._name] = [0, 0.0, 0.0, 0.0, 0.0]
        self_wall = wall - self._children[0]
        row[0] += 1
        row[1] += wall
        row[2] += self_wall
        if self._cpu:
            row[3] += self_wall
            row[4] += cpu - self._children[1]
        return False


class Phases:
    """The named phases of one loop, entered from ONE thread (the
    loop's); any thread may call :meth:`snapshot`.

    ``with phases("sample", active=3):`` opens
    ``TraceAnnotation("<prefix>sample", active=3)`` and on exit adds to
    the row of ``sample``: ``[count, wall_s, self_wall_s,
    timed_self_wall_s, timed_self_cpu_s]``. Wall is
    ``time.monotonic()``; the self columns leave out the phases entered
    inside this one. The last two columns cover the entries whose CPU
    was timed too (the calling thread's ``time.thread_time()``): their
    self wall and self CPU seconds. Wall minus CPU of a phase that only
    computes on the host is time the thread was not running: waiting
    for the GIL, or descheduled.

    Always on. With no capture running an annotation costs about half
    a microsecond, but the thread's CPU clock is a system call: 6 us in
    a small process on the v5e's sandboxed host and about 25 us in the
    process that holds the chip (0.1 us for the wall clock). So CPU is
    timed in one turn of ``CPU_EVERY``: :meth:`step` opens a turn, and
    the phases inside a turn follow it. Outside any turn every entry is
    timed. Not rarer than that: timed in one turn of 64 (every 3 s) a
    greedy batch loop read 38-66% off the CPU where one in 8 reads
    5.6%; a phase's wall holds one read of the clock, and sparse reads
    seem to take far longer than 25 us, so the number measured them.
    """

    CPU_EVERY = 8

    def __init__(self, prefix: str = ""):
        self._prefix = prefix
        self._span, self._step_span = _annotations()
        self._rows: Dict[str, List[float]] = {}
        self._stack: List[List[float]] = []
        self._turns = 0
        self._cpu = True

    def __call__(self, name: str, **attrs) -> _Phase:
        return _Phase(self, name, self._span(self._prefix + name, **attrs),
                      self._cpu)

    def step(self, name: str, step_num: int, **attrs) -> _Phase:
        """A phase that is one turn of the loop: a StepTraceAnnotation,
        so a capture's step view groups by ``step_num``."""
        self._cpu = self._turns % self.CPU_EVERY == 0
        self._turns += 1
        return _Phase(self, name, self._step_span(
            self._prefix + name, step_num=step_num, **attrs), self._cpu)

    def snapshot(self) -> Dict[str, List[float]]:
        return {name: list(row) for name, row in list(self._rows.items())}


def device_memory_stats() -> Optional[dict]:
    """Live HBM stats of the first addressable device (bytes in use /
    limit), or None off-device. Cheap enough to poll from monitors."""
    try:
        import jax

        dev = jax.local_devices()[0]
        stats = dev.memory_stats()
        if not stats:
            return None
        return {"bytes_in_use": stats.get("bytes_in_use", 0),
                "bytes_limit": stats.get("bytes_limit", 0),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
                "platform": dev.platform}
    except Exception:  # noqa: BLE001
        return None
