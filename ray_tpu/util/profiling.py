"""What a loop and a program say about themselves to a profiler capture
(the XLA/jax profiler, whose traces open in TensorBoard/Perfetto) and,
with no capture running, to whoever asks for ``stats()``.

Two entry points:

- :class:`Phases` — the named phases of one loop: each is a
  TraceAnnotation on the profiler's clock AND a row of always-on
  counters (count, wall seconds and, sampled, the thread's CPU
  seconds) AND a distribution of its wall, so the same names read the
  same work with and without a capture.

- :func:`part` — the named part of a block that a device operation
  belongs to (``jax.named_scope`` under ONE vocabulary, :data:`PARTS`):
  metadata of the compiled instructions, read from a capture by
  ``benchmark/layer_metrics/_dev_ms_by_part.py``.

A capture itself is started with ``jax.profiler`` directly. The spans
degrade to no-ops when jax's profiler is unavailable (e.g. a worker
without jax initialized). This module imports without jax.
"""

from __future__ import annotations

import bisect
import contextlib
import functools
import time
from typing import Dict, List


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
# What a gated short-convolution mixer adds (PR 57, ``models/lfm2.py``):
# its in and out projections, the three taps of the causal depthwise
# convolution, and the two elementwise gates around it. The reader's
# side is ``benchmark/layer_metrics/parts/lfm2.json``.
CONV_PARTS = ("conv_proj", "short_conv", "conv_gate")
# What a TRAINED expert layer adds (PR 57): the kernel of the grouped
# product's weight gradient, under its custom call's own name
# (``ops/pallas/grouped_matmul.py``; the rows' gradient is the forward's
# kernel under the forward's name). A tuple of its own because
# ``PARTS`` is ``parts/base.json``, a file of the benchmark's; the
# reader's side is ``benchmark/layer_metrics/parts/expert_grad.json``.
EXPERT_GRAD_PARTS = ("grouped_expert_matmul_dw",)
_VOCABULARY = (PARTS + SSM_PARTS + BLOCK_PARTS + LOOP_PARTS + CONV_PARTS
               + EXPERT_GRAD_PARTS)


def part(name: str):
    """``jax.named_scope(name)`` for a ``name`` of :data:`PARTS` (or of
    any other tuple here whose name ends in ``PARTS``): what is traced inside
    belongs to that part of the block. Checked while tracing; nothing
    runs for it on the device or in a loop's turn."""
    if name not in _VOCABULARY:
        raise ValueError(f"{name!r} is no part of a block: one of "
                         f"{_VOCABULARY}")
    import jax

    return jax.named_scope(name)


# The edges every distribution of walls shares: geometric, sixteen a
# doubling from 16 us to a little over 4 s, so a bucket is 4.4% wide and
# a percentile read from the counts (linear inside its bucket, as
# ``benchmark/layer_metrics/_phase_walls.py`` reads the difference of two
# snapshots) lies within that of the exact one wherever in its bucket
# the walls sit. ``counts`` has one bucket more
# than there are edges: ``counts[i]`` holds the walls in
# ``[WALL_EDGES_S[i - 1], WALL_EDGES_S[i])``, the first what is shorter
# than every edge, the last what is longer.
WALL_EDGES_S = tuple(16e-6 * 2.0 ** (i / 16) for i in range(18 * 16 + 1))


def wall_counts() -> List[int]:
    """An empty distribution over :data:`WALL_EDGES_S`."""
    return [0] * (len(WALL_EDGES_S) + 1)


def count_wall(counts: List[int], wall_s: float) -> None:
    counts[bisect.bisect_right(WALL_EDGES_S, wall_s)] += 1


class _Phase:
    """One entry into a phase: the annotation, and on exit the row and
    the wall's bucket."""

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
            self._owner._walls[self._name] = wall_counts()
        count_wall(self._owner._walls[self._name], wall)
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

    Beside the row each phase keeps its WALL as a distribution
    (:meth:`walls`, over :data:`WALL_EDGES_S`): a sum says what a turn
    costs, a percentile of a client's gaps is made by the turns that
    were long.

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
        self._walls: Dict[str, List[int]] = {}
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

    def walls(self) -> dict:
        """``{"edges_s": [...], "counts": {phase: [...]}}``: every
        phase's wall (the whole of an entry, children included) counted
        into :data:`WALL_EDGES_S`' buckets, one increment an exit."""
        return {"edges_s": list(WALL_EDGES_S),
                "counts": {name: list(counts) for name, counts
                           in list(self._walls.items())}}
