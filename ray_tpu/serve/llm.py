"""LLM serving: continuous batching on a TPU replica.

Reference delegates this wholesale to vLLM
(``python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py``);
here it's native. One engine thread (:meth:`LLMEngine._loop_once`) turns
an Orca-style loop over fixed slots: advance ALL active slots one token in
one jitted decode step and take their tokens on the device
(:func:`greedy_ids` for a turn whose slots are all greedy,
:func:`sample_ids` for any other), then give back what a window has passed
and grow the running slots' block tables, and admit waiting requests into
free slots (one bucketed prefill each; a long prompt or a prefix-cache
suffix one chunk a turn). The host fetches one int32 a slot, never a row
of the vocabulary, and it works one step behind the device: step N+1 is
dispatched from the ids step N left ON the device, and step N's fetch and
bookkeeping, the freeing, the growing and the admitting run while step
N+1 does (:meth:`LLMEngine._decode_turn`).

The KV cache is paged by default: a shared pool of fixed-size blocks with
host-side block tables. The model stands behind
:mod:`ray_tpu.models.serving`, which describes the whole interface: it
brings its pool(s), its allocator and its two programs, prefill (one
compile a padded-length bucket) and decode (one compile), and one more
builder for each mechanism it has: the chunked prefill, the block copy
under the radix prefix cache (:mod:`ray_tpu.models.prefix_cache`), KV
inject for prefill/decode disaggregation, speculation's batched verify,
and ``kv_cache="slot"`` (a flat ``max_seq`` reservation a slot, handed
over in the paged cache's shape, so the loop below is written once). The
engine builds no program of its own but the three that pick tokens, and
refuses by name a mechanism the model has no builder for. Shapes are
fixed, so nothing compiles in steady state. A model may bring a block
step in place of the decode step (``block_denoise``: a slot then holds a
block of positions that a step decides some of, and a step that finds it
wholly decided commits it), which the same loop runs one step ahead of
the host (:meth:`LLMEngine._dispatch_block`).
"""

from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu.util import profiling, tracing


@dataclasses.dataclass
class _Request:
    prompt: List[int]
    max_tokens: int
    temperature: float
    eos_token: Optional[int]
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    output: List[int] = dataclasses.field(default_factory=list)
    # when the engine appended each token of ``output``, on
    # time.monotonic(): written BEFORE the token, by the engine thread
    # alone, so a poll() that sees a token finds its time (no lock).
    # :meth:`land` writes the three in this order: time, token, then
    # ``fresh``, so a reader that ``fresh`` woke finds both
    landed_at: List[float] = dataclasses.field(default_factory=list)
    # what wakes this request's reader (LLMEngine.wait_fresh): set after
    # every landing and at the request's end. A request has one if it
    # came through submit() or submit_prefilled(), whose callers read it
    # as it grows; generate()'s caller waits for ``done`` alone
    fresh: Optional[threading.Event] = None
    error: Optional[str] = None
    enqueued_at: float = dataclasses.field(default_factory=time.monotonic)
    # KV handed off from a prefill replica (PD disaggregation): dict with
    # "k"/"v" (layers, len, kv_heads, hd) numpy + "logits" of the last
    # prompt token; admission injects instead of prefilling.
    preload: Optional[dict] = None
    # per-request speculation override: None = engine default;
    # {"enabled": bool, "k": Optional[int]} normalized by _parse_req_spec
    spec: Optional[dict] = None
    # multi-tenant identity: per-tenant fair-share admission on decode
    # slots and on the radix-cache insert budget key off this
    tenant: Optional[str] = None
    # set by LLMEngine.cancel (replica-side abort): the engine thread
    # notices at its next finish check and frees the slot + blocks
    cancelled: bool = False
    # lifecycle, on enqueued_at's clock (time.monotonic): first admission
    # into a slot, first token appended by the engine, first chunk handed
    # to a caller by poll() (None for generate()), and the end
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    first_picked_at: Optional[float] = None
    finished_at: Optional[float] = None
    preemptions: int = 0
    # the finished request's row in LLMEngine's ring: a poll() that comes
    # after the finish still fills in first_picked_at
    record: Optional[list] = None
    # the submitting task's span context, when its caller traces
    trace_ctx: Optional[Dict[str, str]] = None
    # the request's place in the order the engine took requests from its
    # queue: with a token's position, what that token's draw is keyed by
    number: int = 0

    def land(self, tokens: List[int], at: float) -> None:
        """The engine thread's one way to hand over tokens: their time,
        the tokens, then the reader's wake-up (``Event.set`` takes the
        event's own lock, which nobody holds for long; no other lock)."""
        self.landed_at.extend([at] * len(tokens))
        self.output.extend(tokens)
        if self.fresh is not None:
            self.fresh.set()

    def end(self) -> None:
        """Finished, failed or cancelled, with ``error`` written by now:
        a blocked generate() and a waiting reader both wake."""
        self.done.set()
        if self.fresh is not None:
            self.fresh.set()


def _parse_req_spec(speculation) -> Optional[dict]:
    """Normalize a per-request speculation override (None / bool / dict
    with "enabled" and/or "k").

    Overrides only restrict what the engine already does: on an engine
    built without speculation they are validated then no-ops (clients
    need not know replica config to send requests), and a requested k
    above the engine's spec_k clamps to spec_k (the compiled verify
    window is sized at engine build)."""
    if speculation is None:
        return None
    if isinstance(speculation, bool):
        return {"enabled": speculation, "k": None}
    if isinstance(speculation, dict):
        unknown = set(speculation) - {"enabled", "k"}
        if unknown:
            raise ValueError(
                f"per-request speculation has unknown fields "
                f"{sorted(unknown)}; overridable: ['enabled', 'k']")
        k = speculation.get("k")
        if k is not None and int(k) <= 0:
            raise ValueError("per-request speculation k must be positive")
        return {"enabled": bool(speculation.get("enabled", True)),
                "k": None if k is None else int(k)}
    raise ValueError("per-request speculation must be a bool or dict")


def greedy_ids(logits):
    import jax.numpy as jnp

    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sample_ids(logits, temperature, key, request, position):
    """One token id a row of ``logits``, picked on the device. A row
    whose ``temperature`` is <= 0 takes :func:`greedy_ids`' argmax; any
    other takes ONE exact draw from ``softmax(logits / max(T, 1e-5))`` in
    float32 (Gumbel-max: no top-k, no truncation). Each draw has a stream
    of its own: ``key`` folded with the row's ``request`` number, then
    with the ``position`` in its sequence of the token drawn, so a
    token's stream does not depend on which slot its request sits in, on
    which turn draws it, or on who shares the turn."""
    import jax
    import jax.numpy as jnp

    logits = logits.reshape(temperature.shape[0], -1)

    def draw(row, t, r, p):
        stream = jax.random.fold_in(jax.random.fold_in(key, r), p)
        return jax.random.categorical(
            stream, row.astype(jnp.float32) / jnp.maximum(t, 1e-5))

    drawn = jax.vmap(draw)(logits, temperature, request, position)
    return jnp.where(temperature > 0.0, drawn,
                     greedy_ids(logits)).astype(jnp.int32)


def merge_ids(host_ids, device_ids):
    """A decode step's input ids when the step before it is still in
    flight: a slot keeps the id that step left on the device, except
    where the host seated a request since and knows its last token
    (``host_ids`` >= 0; -1 elsewhere)."""
    import jax.numpy as jnp

    return jnp.where(host_ids >= 0, host_ids, device_ids)


@dataclasses.dataclass
class _Flight:
    """A decode step the device has been given and the host has not
    read: its number, its ids (and the model's counters of it) still on
    the device, and who ran in it."""
    step: int
    ids: Any
    counters: Any
    reqs: List[Optional[_Request]]      # by slot; None: masked out
    sampled: int
    # a block step's: the tokens each slot commits in it (0: it only
    # decides), known at dispatch; None: a decode step, one a slot
    coming: Optional[np.ndarray] = None


def seat_blocks(ids, decided, host_ids, host_decided, seated):
    """The slots' blocks when requests were seated since the step in
    flight was dispatched: a ``seated`` slot takes the block the host
    knows (its prompt's tail decided, the rest not), any other keeps
    what the step before left on the device."""
    import jax.numpy as jnp

    seated = seated[:, None]
    return (jnp.where(seated, host_ids, ids),
            jnp.where(seated, host_decided, decided))


class LLMEngine:
    """Single-replica continuous-batching engine.

    ``config`` is a model's config object. The engine takes the model
    through :func:`ray_tpu.models.serving.serving_model`: its cache(s),
    its prefill and decode programs and its allocator (one for each kind
    of KV state it keeps). A mechanism the model has no builders for
    raises ``ValueError`` here, naming it. A model with no decode step
    brings ``block_denoise`` and is run by the block turn: its config's
    ``block_length``, ``mask_token_id`` and ``step_quota`` (the rule's
    positions a step) are the generation's settings, and the engine has
    no option for them.

    ``kv_cache="paged"`` (default) backs the slots with the model's
    block-table pool: HBM per request tracks tokens actually cached,
    ``kv_pool_tokens`` bounds the total, and a request that outgrows the
    pool preempts the youngest other slot (vLLM-style recompute
    preemption: its blocks are freed and it re-queues with
    prompt+generated-so-far as the new prompt). ``kv_cache="slot"``, dense
    decoder only, keeps the flat per-slot ``max_seq`` reservation;
    ``speculation`` runs on it only.

    ``prefill_chunk`` prefills prompts longer than it one chunk a turn;
    ``prefix_cache="radix"`` (or a ``prefix_cache_bytes`` budget; paged
    only) shares cached prompt blocks between requests and prefills only
    the uncached suffix. Both are off by default.

    ``seed`` makes the weights where ``params`` is None, and the key of
    every draw at a temperature (:func:`sample_ids`): the same ``seed``
    and the same requests in the same order are answered the same tokens,
    another ``seed`` others. (Speculation's acceptance draws are the
    host's and keyed by the step count.)
    """

    def __init__(self, config=None, params=None, *, num_slots: int = 8,
                 max_seq: Optional[int] = None, model: str = "tiny",
                 seed: int = 0, prefix_cache: Optional[str] = None,
                 prefix_cache_bytes: Optional[int] = None,
                 kv_cache: str = "paged",
                 kv_pool_tokens: Optional[int] = None,
                 kv_block_size: int = 64,
                 prefill_chunk: Optional[int] = None,
                 speculation=None,
                 spec_k: int = 4):
        import collections

        import jax

        from ray_tpu.common.compile_cache import compile_cache_counts
        from ray_tpu.models.serving import serving_model

        self.model = serving_model(config, model)
        self.config = self.model.config
        self.num_slots = num_slots
        self.max_seq = max_seq or self.config.max_seq
        self.kv_cache = kv_cache
        if kv_cache == "paged":
            if kv_block_size <= 0 or 2048 % kv_block_size:
                # must divide the prompt padding buckets, or a padded
                # prompt is no multiple of it and crashes every prefill
                raise ValueError(
                    f"kv_block_size={kv_block_size} must divide 2048")
            build_cache = functools.partial(
                self.model.paged, block_size=kv_block_size,
                pool_tokens=kv_pool_tokens or num_slots * self.max_seq)
        elif kv_cache == "slot":
            build_cache = self._builder("slot")
        else:
            raise ValueError(f"kv_cache={kv_cache!r}: 'paged' or 'slot'")
        # the device this replica's process holds, as jax reports it —
        # stats() carries it so a driver that stays off jax can tell
        from ray_tpu.common import tpu_detect

        self._dev = dev = jax.devices()[0]
        self._device = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices()),
                        "granted_chips": tpu_detect.granted_chips}
        self._compile_cache = compile_cache_counts()
        if params is None:
            params = self.model.init_params(jax.random.key(seed))
        # on a TPU the weights lie as the model's programs read them
        # (``place``, where the model has it): re-laid leaves are donated,
        # so the tree that is served is this one and no other
        self._weights_relaid_bytes = 0
        place = getattr(self.model, "place", None)
        if place is not None and jax.default_backend() == "tpu":
            given = jax.tree.leaves(params)
            params = place(params)
            self._weights_relaid_bytes = sum(
                new.nbytes for old, new in zip(given,
                                               jax.tree.leaves(params))
                if new is not old)
        self.params = params
        programs = build_cache(params, num_slots=num_slots,
                               max_seq=self.max_seq)
        self._page = programs.page
        self._alloc = programs.alloc
        self._cache = programs.cache
        if self._weights_relaid_bytes:
            # re-laid leaves are committed to the device, so what a program
            # returns is: a cache that began uncommitted would make a
            # program's first call and its later ones two compiles, the
            # second wherever a prompt first meets a bucket again (no copy)
            self._cache = jax.device_put(self._cache, dev)
        self._decode = programs.decode
        self._prefill = programs.prefill
        self._inject = programs.inject
        # bucketed padded length of a prompt (the cache's own rule)
        self._prompt_pad = programs.pad
        self._counter_names = programs.counters
        # a model without a decode step generates by blocks: its block
        # step and deciding program, a block a slot on the device, and
        # the host's counts of it (what a step will do to a slot follows
        # from them, never from the ids)
        self._block_step = None
        self._step_rows = 1         # KV rows a step writes after a length
        if self._decode is None:
            import jax.numpy as jnp

            self._block_step, self._block_decide = self._builder(
                "block_denoise")(params, programs)
            self._step_rows = B = self.config.block_length
            self._block_state = (jnp.zeros((num_slots, B), jnp.int32),
                                 jnp.zeros((num_slots, B), bool))
            self._seat_blocks = jax.jit(seat_blocks)
            self._blk_ids = np.zeros((num_slots, B), np.int32)
            self._blk_decided = np.zeros((num_slots, B), bool)
            self._blk_seated = np.zeros(num_slots, bool)
            self._blk_undecided = np.full(num_slots, B, np.int64)
            self._blk_quota = np.zeros(num_slots, np.int64)
            self._blk_skip = np.zeros(num_slots, np.int64)
            self._block_counts = dict.fromkeys(
                ("block_steps", "slot_steps", "commit_steps",
                 "blocks_committed", "positions_decided"), 0)
        if self._counter_names:
            # a program's counters are an output of it alone; the
            # engine takes them out of the cache it hands on
            # (_take_counters), so every program is given None there
            self._cache = dict(self._cache, counters=None)
        # Chunked prefill (vLLM-class / Sarathi): prompts longer than the
        # chunk prefill one fixed-size chunk per engine iteration,
        # interleaved with decode steps of the other slots — a long
        # prompt no longer stalls everyone's TTFT for its whole prefill.
        self._chunk_prefill = None
        if prefill_chunk is not None:
            if prefill_chunk <= 0:
                raise ValueError("prefill_chunk must be positive")
            self._chunk_prefill = self._builder("chunked_prefill")(
                params, programs, prefill_chunk)
        self.prefill_chunk = prefill_chunk
        # slot -> {"req", "tokens", "pos"} for in-progress chunked prefills
        self._prefilling: Dict[int, dict] = {}
        self._chunks_run = 0
        # Speculative decoding (ray_tpu.models.speculation): a pluggable
        # proposer ("ngram" prompt lookup or a small "draft" model in
        # lockstep) guesses up to k tokens per slot and ONE batched
        # verify forward scores every slot's window — per-slot under
        # continuous batching; slots without proposals degenerate to a
        # plain decode row in the same program. Greedy acceptance only
        # skips compute, never changes outputs; temperature > 0 keeps
        # the target distribution via residual resampling.
        self._proposer = None
        self._spec_cfg = None
        if speculation is not None:
            import jax.numpy as jnp

            from ray_tpu.models.speculation import SpeculationConfig

            build_verify = self._builder("speculative_verify")
            cfg = SpeculationConfig.parse(speculation, default_k=spec_k)
            if kv_cache != "slot":
                raise ValueError(
                    "speculation currently requires kv_cache='slot'")
            self._spec_cfg = cfg
            self._spec_verify, self._spec_fix_len = build_verify(params)
            # device-side argmax so greedy verify rounds transfer (B, C)
            # ids instead of (B, C, vocab) logits
            self._spec_argmax = jax.jit(
                lambda logits: jnp.argmax(logits, axis=-1))
            self._proposer = cfg.build_proposer(
                self.config, num_slots=num_slots, max_seq=self.max_seq)
            spec_k = cfg.k
            speculation = cfg.method
        self.speculation = speculation
        self.spec_k = spec_k
        self._spec_proposed = 0
        self._spec_accepted = 0
        # a turn's tokens are taken on the device, so the fetch moves 4
        # bytes a slot and not a row of the vocabulary: a turn whose
        # every slot is greedy runs jit_greedy_ids, any other
        # jit_sample_ids (neither is jit_step*: decode_step_dev_ms.*
        # reads that name)
        self._key = jax.random.key(seed)
        self._greedy_ids = jax.jit(greedy_ids)
        self._sample_ids = jax.jit(sample_ids)
        self._merge_ids = jax.jit(merge_ids)
        # what sample_ids is given beside the logits, a row a slot: the
        # occupant's temperature and number. The device's copy is made
        # again only after a slot got a new occupant
        self._draw_temp = np.zeros(num_slots, np.float32)
        self._draw_request = np.zeros(num_slots, np.int32)
        self._draw_rows = None
        self._requests_taken = 0
        self._greedy_turns = 0
        self._sampled_turns = 0
        self._sampled_tokens = 0
        # Prefix reuse across requests, OFF by default: on with
        # prefix_cache="radix" or when a byte budget is given. The radix
        # tree of ray_tpu.models.prefix_cache shares the prompt's pool
        # blocks read-only between requests (block-level, zero-copy,
        # copy-on-write divergence), so a shared-system-prompt request
        # prefills ONLY its new tokens.
        mode = prefix_cache
        if mode is None:
            mode = "radix" if (prefix_cache_bytes or 0) > 0 else "off"
        if mode not in ("radix", "off"):
            raise ValueError(f"prefix_cache={mode!r}: 'radix' or 'off'")
        self._prefix_mode = mode
        self._prefix_match_faults = 0
        self._prefix_insert_faults = 0
        self._fair_share_skips = 0
        self._radix = None
        if mode == "radix":
            from ray_tpu.models.prefix_cache import RadixPrefixCache

            build_copy = self._builder("block_copy")
            if kv_cache != "paged":
                raise ValueError("prefix_cache='radix' requires "
                                 "kv_cache='paged' (it shares pool blocks)")
            bytes_per_block = self.model.block_bytes(programs)
            if prefix_cache_bytes is None:
                # default: the tree may cache up to half the pool —
                # pool-pressure eviction reclaims cold blocks anyway,
                # the budget just bounds steady-state residency
                prefix_cache_bytes = ((self._page.num_blocks - 1) // 2
                                      * bytes_per_block)
            self._radix = RadixPrefixCache(
                self._alloc, bytes_per_block=bytes_per_block,
                budget_bytes=prefix_cache_bytes)
            self._block_copy = build_copy(programs)
            if self._chunk_prefill is None:
                # suffix-only prefill after a radix hit rides the chunked
                # kernel (row-level scatter, arbitrary start) even when
                # the engine wasn't configured for chunked prefill
                self._chunk_prefill = self._builder("chunked_prefill")(
                    params, programs)
        self._prefix_cache_bytes = prefix_cache_bytes or 0

        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._waiting: "collections.deque[_Request]" = collections.deque()
        self._pending: Dict[str, dict] = {}      # streaming submit/poll
        self._pending_lock = threading.Lock()
        self._slots: List[Optional[_Request]] = [None] * num_slots
        self._last_token = np.zeros(num_slots, np.int32)
        # host mirror of cached tokens per slot (= device cache length)
        self._slot_len = np.zeros(num_slots, np.int64)
        self._admit_seq = np.zeros(num_slots, np.int64)  # preempt-victim age
        self._admit_counter = 0
        self._stop = threading.Event()
        # decode steps whose ids the host has read, and the one the
        # device was given last and the host has not read
        self._steps = 0
        self._flight: Optional[_Flight] = None
        self._turns_overlapped = 0
        self._turns_drained = 0
        self._surplus_dropped = 0
        self._tokens_generated = 0
        self._preemptions = 0
        # the model's own counters (an expert layer's loads), summed
        # over every decode step, and over every prefill, since the
        # engine started
        self._model_counters = np.zeros(len(self._counter_names))
        self._model_counters_prefill = np.zeros(len(self._counter_names))
        self._window_blocks_freed = 0
        # the loop's named phases (spans in a profiler capture, counters
        # in stats()) and the last finished requests' lifecycle records
        self._phases = profiling.Phases("rt.engine.")
        # a token's way to its caller (poll() and wait_fresh() write
        # these, under _pending_lock) and what the admissions were made
        # of (the engine thread's): stats()["delivery"],
        # stats()["admissions"]
        self._delivery = dict.fromkeys(
            ("polls", "polls_empty", "tokens_picked", "waits_woken",
             "waits_timed_out"), 0)
        self._pickup_walls = profiling.wall_counts()
        self._admissions = dict.fromkeys(
            ("prefills", "prompt_tokens", "padded_tokens",
             "turns_admitting", "also_waiting"), 0)
        self._finished = 0
        self._recent: "collections.deque[list]" = collections.deque(
            maxlen=512)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="llm-engine")
        self._thread.start()

    # ------------------------------------------------------------- public
    # the builders a model may lack (ray_tpu.models.serving), each in
    # the words of the engine's option that needs it
    _MECHANISMS = {
        "slot": "kv_cache='slot'",
        "speculative_verify": "speculation",
        "block_copy": "a prefix cache (prefix_cache / prefix_cache_bytes)",
        "chunked_prefill": "chunked prefill (prefill_chunk)",
        "kv_shape": "KV inject / extract (llm_pd, submit_prefilled)",
        "block_denoise": "generation by blocks (it has no decode step "
                         "either)",
    }

    def _builder(self, name: str):
        """The model's builder of that name, or the refusal of the
        mechanism it stands for: a model has what it has builders for."""
        builder = getattr(self.model, name, None)
        if builder is None:
            raise ValueError(
                f"{type(self.config).__name__} is not served with "
                f"{self._MECHANISMS[name]}: the model has no builders for "
                "it")
        return builder

    def _check_vocab(self, prompt: List[int]) -> None:
        """Reject out-of-vocab prompt token ids at submission. On device
        the embed gather would clamp silently, but host-side speculation
        indexes probability rows by proposed token — and an ngram
        proposer re-proposes PROMPT tokens, so one malformed request
        could crash an engine step shared by every in-flight slot."""
        V = self.config.vocab_size
        for t in prompt:
            if not 0 <= int(t) < V:
                raise ValueError(
                    f"prompt token {t} out of vocab range [0, {V})")

    def generate(self, prompt: List[int], max_tokens: int = 64,
                 temperature: float = 0.0,
                 eos_token: Optional[int] = None,
                 timeout_s: float = 300.0,
                 speculation=None, tenant: Optional[str] = None
                 ) -> List[int]:
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) + max_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_tokens ({max_tokens}) "
                f"exceeds max_seq {self.max_seq}")
        self._check_vocab(prompt)
        req = _Request(list(prompt), max_tokens, temperature, eos_token,
                       spec=_parse_req_spec(speculation), tenant=tenant,
                       trace_ctx=tracing.current_context())
        self._queue.put(req)
        if not req.done.wait(timeout_s):
            raise TimeoutError("generation timed out")
        if req.error:
            raise RuntimeError(req.error)
        return req.output

    def submit(self, prompt: List[int], max_tokens: int = 64,
               temperature: float = 0.0,
               eos_token: Optional[int] = None,
               speculation=None, tenant: Optional[str] = None) -> str:
        """Enqueue without blocking; poll with :meth:`poll`, after
        :meth:`wait_fresh` or at the caller's own pace (drives the
        proxy's SSE token streaming)."""
        import uuid

        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) + max_tokens > self.max_seq:
            raise ValueError("prompt + max_tokens exceeds max_seq")
        self._check_vocab(prompt)
        req = _Request(list(prompt), max_tokens, temperature, eos_token,
                       spec=_parse_req_spec(speculation), tenant=tenant,
                       trace_ctx=tracing.current_context(),
                       fresh=threading.Event())
        rid = uuid.uuid4().hex
        with self._pending_lock:
            self._pending[rid] = {"req": req, "sent": 0}
        self._queue.put(req)
        return rid

    def cancel(self, request_id: str) -> bool:
        """Replica-side request abort: mark the request cancelled and
        drop its poll entry. The engine thread notices at its next
        finish check and frees the slot — including the refcount drop
        on any radix-shared blocks, which is why cancellation must
        never free blocks directly from the caller thread."""
        with self._pending_lock:
            ent = self._pending.pop(request_id, None)
        if ent is None:
            return False
        ent["req"].cancelled = True
        # its reader's next poll() finds no entry: done
        ent["req"].fresh.set()
        return True

    def submit_prefilled(self, prompt: List[int], k, v, logits,
                         max_tokens: int = 64, temperature: float = 0.0,
                         eos_token: Optional[int] = None) -> str:
        """Decode-side half of PD disaggregation: admit a request whose
        prompt KV was computed by a prefill replica. k/v are
        (layers, len(prompt), kv_heads, head_dim) arrays, logits the last
        prompt position's logits."""
        import uuid

        kv_shape = self._builder("kv_shape")
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) + max_tokens > self.max_seq:
            raise ValueError("prompt + max_tokens exceeds max_seq")
        k, v = np.asarray(k), np.asarray(v)
        want = kv_shape(len(prompt))
        if k.shape != want or v.shape != want:
            # caller thread: surface the mismatch to the submitter rather
            # than blowing up the engine loop for every in-flight request
            raise ValueError(
                f"prefilled KV shape {k.shape}/{v.shape} != expected {want}")
        req = _Request(list(prompt), max_tokens, temperature, eos_token,
                       preload={"k": k, "v": v,
                                "logits": np.asarray(logits)},
                       trace_ctx=tracing.current_context(),
                       fresh=threading.Event())
        rid = uuid.uuid4().hex
        with self._pending_lock:
            self._pending[rid] = {"req": req, "sent": 0}
        self._queue.put(req)
        return rid

    def wait_fresh(self, request_id: str, timeout: float) -> None:
        """Block until the engine has landed something for the request
        since the last call (a token, its end), at most ``timeout``
        seconds; then :meth:`poll`. The order is wait, clear, poll: what
        lands after the clear leaves the event set, so the next call
        returns at once and nothing waits out a ``timeout`` unseen."""
        with self._pending_lock:
            ent = self._pending.get(request_id)
        if ent is None:
            return          # cancelled, swept or drained: poll() says so
        fresh = ent["req"].fresh
        woken = fresh.wait(timeout)
        fresh.clear()
        with self._pending_lock:
            self._delivery["waits_woken" if woken
                           else "waits_timed_out"] += 1

    def poll(self, request_id: str) -> Dict[str, Any]:
        """New tokens since the last poll + done flag. The entry is dropped
        once fully drained after completion."""
        with self._pending_lock:
            ent = self._pending.get(request_id)
            if ent is None:
                return {"chunks": [], "done": True}
            now = ent["last_poll"] = time.monotonic()
            req = ent["req"]
            out = list(req.output)   # snapshot (engine thread appends)
            chunks = out[ent["sent"]:]
            self._delivery["polls"] += 1
            if chunks:
                # how long the oldest token this poll hands over lay
                # there: every token is in one such wait
                self._delivery["tokens_picked"] += len(chunks)
                profiling.count_wall(self._pickup_walls,
                                     now - req.landed_at[ent["sent"]])
            else:
                self._delivery["polls_empty"] += 1
            ent["sent"] = len(out)
            if chunks and req.first_picked_at is None:
                req.first_picked_at = now
                if req.record is not None:
                    req.record[3] = req.first_picked_at
            finished = req.done.is_set() and ent["sent"] >= len(req.output)
            if finished:
                del self._pending[request_id]
            if req.error:
                raise RuntimeError(req.error)
            return {"chunks": chunks, "done": finished}

    def stats(self) -> Dict[str, Any]:
        out = {"steps": self._steps,
               "tokens_generated": self._tokens_generated,
               "active_slots": sum(s is not None for s in self._slots),
               "queued": self._queue.qsize() + len(self._waiting),
               "prefix_hits": 0, "prefix_misses": 0,
               "prefill_chunks_run": self._chunks_run,
               "prefilling_slots": len(self._prefilling),
               "spec_proposed": self._spec_proposed,
               "spec_accepted": self._spec_accepted,
               "spec_acceptance_rate": (
                   round(self._spec_accepted / self._spec_proposed, 4)
                   if self._spec_proposed else None),
               "speculation": self.speculation,
               "kv_cache": self.kv_cache,
               # weights the model's ``place`` laid out anew (0: none)
               "weights_relaid_bytes": self._weights_relaid_bytes}
        if self._proposer is not None:
            out.update(self._proposer.stats())
        free = self._alloc.free_blocks()
        if free is not None:            # a pool of blocks, not a reservation
            out.update(
                preemptions=self._preemptions,
                kv_blocks_free=free,
                kv_blocks_total=self._page.num_blocks - 1,
                kv_block_size=self._page.block_size)
        # one row for each kind of KV state, where the allocator keeps
        # several, with the tokens a decode step reads there now
        pools = self._alloc.pools(
            [int(self._slot_len[s]) for s in range(self.num_slots)
             if self._slots[s] is not None])
        if pools:
            out["kv_pools"] = pools
            out["window_blocks_freed"] = self._window_blocks_freed
        if self._counter_names:
            out["model_counters"] = dict(zip(
                self._counter_names, self._model_counters.tolist()))
            out["model_counters_prefill"] = dict(zip(
                self._counter_names, self._model_counters_prefill.tolist()))
        pc = {"mode": self._prefix_mode,
              "match_faults": self._prefix_match_faults,
              "insert_faults": self._prefix_insert_faults,
              "budget_bytes": self._prefix_cache_bytes}
        if self._radix is not None:
            pc.update(self._radix.stats())
            out["prefix_hits"] = pc["hits"]
            out["prefix_misses"] = pc["misses"]
        out["prefix_cache"] = pc
        out["fair_share_skips"] = self._fair_share_skips
        # decode turns by the program that took their tokens, and the
        # tokens drawn at a temperature (first tokens included)
        out["sampling"] = {"greedy_turns": self._greedy_turns,
                           "sampled_turns": self._sampled_turns,
                           "sampled_tokens": self._sampled_tokens}
        # decode steps dispatched behind one whose ids the host had not
        # read, and with none in flight (the first, after a drain,
        # speculation's); ids of a step that ran past its request's end
        out["turns"] = {"overlapped": self._turns_overlapped,
                        "drained": self._turns_drained,
                        "surplus_dropped": self._surplus_dropped}
        if self._block_step is not None:
            # steps of the block turn (``steps`` counts them too), the
            # slots that ran in them, the slot-steps among those that
            # only committed, the blocks they committed and the
            # positions the others decided
            out.update(self._block_counts)
        mem = self._dev.memory_stats() or {}
        out["device"] = dict(self._device,
                             peak_bytes_in_use=mem.get("peak_bytes_in_use"))
        out["compile_cache"] = dict(self._compile_cache)
        out["phases"] = self._phases.snapshot()
        # each phase's wall as a distribution (profiling.WALL_EDGES_S)
        out["phase_walls"] = self._phases.walls()
        # poll()s, those that found no token, the tokens handed over, the
        # wait_fresh()s that the engine ended and those that ran into
        # their time-out, and, over the same edges, how long the oldest
        # of a poll's tokens had lain in ``output`` (one count for each
        # poll that took any)
        out["delivery"] = dict(self._delivery,
                               pickup_wall_counts=list(self._pickup_walls))
        # blocking prefill programs run, their prompts' tokens and their
        # buckets', the turns that ran at least one, and the requests
        # still waiting behind each one picked (summed)
        out["admissions"] = dict(self._admissions)
        out["requests"] = {"finished": self._finished,
                           "recent": [list(r) for r in list(self._recent)]}
        return out

    def prefix_digest(self) -> List[int]:
        """Compact advertisement of cached prefixes for prefix-aware
        routing: cumulative 16-token-chunk hashes in the handle's
        ``_RouterState._prefix_hashes`` scheme. Best-effort — the engine
        thread mutates the tree concurrently, so a torn walk returns a
        partial digest rather than an error (it is a routing hint)."""
        try:
            if self._radix is not None:
                return self._radix.digest()
        except Exception:  # noqa: BLE001 — hint only, never a failure
            pass
        return []

    def shutdown(self):
        self._stop.set()
        self._thread.join(timeout=5)

    # ------------------------------------------------------------- engine
    def _inject_kv(self, slot: int, k: np.ndarray, v: np.ndarray,
                   true_len: int):
        """Pad external KV rows to a bucket and write them into `slot`,
        for which the caller has ensure()d blocks for ``true_len``."""
        import jax.numpy as jnp

        P = self._prompt_pad(true_len)
        pad = P - k.shape[1]
        if pad > 0:
            widths = ((0, 0), (0, pad), (0, 0), (0, 0))
            k = np.pad(k, widths)
            v = np.pad(v, widths)
        with self._phases("kv_inject", slot=slot):
            self._cache = self._inject(
                self._cache, self._alloc.table_rows(slot), jnp.asarray(k),
                jnp.asarray(v), true_len, slot)

    def _free_slot(self) -> Optional[int]:
        for slot in range(self.num_slots):
            if self._slots[slot] is None:
                return slot
        return None

    def _pick_waiting(self) -> int:
        """Index into the waiting deque of the next request to admit.
        FIFO, with two exceptions: a preempted request (non-empty
        output) always resumes first, and under multi-tenant contention
        a tenant already holding its fair share of decode slots yields
        to the first under-share tenant in the queue — PR 18's
        per-client proxy fair share, extended down onto slots so one
        tenant's burst cannot monopolize the engine."""
        if len(self._waiting) == 1 or self._waiting[0].output:
            return 0
        held: Dict[Optional[str], int] = {}
        for s in range(self.num_slots):
            r = self._slots[s]
            if r is not None:
                held[r.tenant] = held.get(r.tenant, 0) + 1
        tenants = {r.tenant for r in self._waiting} | set(held)
        if len(tenants) <= 1:
            return 0
        share = max(1, self.num_slots // len(tenants))
        for i, r in enumerate(self._waiting):
            if held.get(r.tenant, 0) < share:
                if i:
                    self._fair_share_skips += 1
                return i
        return 0  # every tenant at/over share: work-conserving FIFO

    def _radix_match(self, full_prompt: List[int]):
        """Longest cached prefix of the prompt. All but the LAST prompt
        token is eligible, so the block where the suffix prefill and the
        first decode write land is always private — a shared block is
        never written. An injected serve.llm.prefix_match fault degrades
        to cold prefill with a typed counter, never a failed request."""
        from ray_tpu.common import faults

        try:
            faults.fault_point("serve.llm.prefix_match")
        except ConnectionError:
            self._prefix_match_faults += 1
            return None
        m = self._radix.match(full_prompt[:-1])
        return m if m.matched else None

    def _radix_insert(self, req: _Request, toks: List[int], slot: int):
        """Share the slot's full-block prefix into the radix tree —
        zero-copy: the tree increfs the slot's own blocks. Byte-budget
        and per-tenant-fair-share gated; an injected
        serve.llm.prefix_insert fault skips the insert with a typed
        counter (nothing is ever half-inserted)."""
        if self._radix is None:
            return
        from ray_tpu.common import faults

        try:
            faults.fault_point("serve.llm.prefix_insert")
        except ConnectionError:
            self._prefix_insert_faults += 1
            return
        bs = self._page.block_size
        nfull = min(len(toks) // bs,
                    int(np.count_nonzero(self._alloc.tables[slot])))
        if nfull <= 0:
            return
        max_new = None
        tb = self._radix.tenant_blocks
        tenants = set(tb) | {req.tenant}
        if len(tenants) > 1:
            # cache-insert fair share: with several tenants caching,
            # each may pin at most its share of the byte budget
            cap = max(1, self._radix.budget_blocks() // len(tenants))
            max_new = cap - tb.get(req.tenant, 0)
            if max_new <= 0:
                self._fair_share_skips += 1
                return
        blocks = [int(b) for b in self._alloc.tables[slot, :nfull]]
        self._radix.insert(toks[:nfull * bs], blocks, tenant=req.tenant,
                           max_new=max_new)

    def _admit(self):
        import jax.numpy as jnp

        # drain the thread-safe queue into the FIFO admission deque
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            req.number = self._requests_taken
            self._requests_taken = (self._requests_taken + 1) % 2 ** 31
            self._waiting.append(req)
        while self._waiting:
            slot = self._free_slot()
            if slot is None:
                return
            idx = self._pick_waiting()
            req = self._waiting[idx]
            if req.cancelled:
                del self._waiting[idx]
                self._record_finish(req, "cancelled")
                req.end()
                continue
            # preempted requests resume by recomputing prompt+generated
            full_prompt = req.prompt + req.output
            plen = len(full_prompt)
            match = None
            # ensure plen + 1: this iteration's decode step writes the
            # first generated token at position plen, which lives in a NEW
            # block when the prompt is block-aligned. (A block step
            # writes its whole block after the prompt's whole blocks.)
            need = plen - plen % self._step_rows + self._step_rows
            if not self._alloc.fits(need):
                # can never fit, even with the pool idle: fail it rather
                # than deadlock the queue
                del self._waiting[idx]
                req.error = (f"prompt of {plen} tokens exceeds KV "
                             "pool capacity")
                self._record_finish(req, "error")
                req.end()
                continue
            if self._radix is not None and req.preload is None:
                match = self._radix_match(full_prompt)
            shared = match.blocks if match is not None else []
            # watermark: beyond this request's blocks, keep one growth
            # block of headroom per already-active slot, or admission
            # starves running requests into preemption
            headroom = sum(s is not None for s in self._slots)
            if shared:
                # pin the matched blocks FIRST: the pool-pressure eviction
                # below must never reclaim them
                self._alloc.adopt(slot, shared)
            lack = self._alloc.lacking(need, len(shared), headroom)
            if lack and self._radix is not None:
                self._radix.evict_for(lack)
                lack = self._alloc.lacking(need, len(shared), headroom)
            if lack or not self._alloc.ensure(slot, need):
                self._alloc.release(slot)  # un-pin the match
                return  # picked request waits for blocks (no bypass)
            if match is not None and match.cow is not None:
                # copy-on-write at the divergence block: ensure() placed a
                # private block at the first position past the shared
                # prefix; device-copy the cached block's rows into it, so
                # the suffix prefill can resume MID-BLOCK at the divergence
                # offset while the cached original stays read-only for its
                # other references.
                with self._phases("block_copy", slot=slot):
                    self._cache = self._block_copy(
                        self._cache, match.cow[0],
                        int(self._alloc.tables[slot, len(shared)]))
            del self._waiting[idx]
            if req.admitted_at is None:
                req.admitted_at = time.monotonic()
            matched = match.matched if match is not None else 0
            if self._block_step is not None:
                self._admit_block(slot, req, full_prompt)
                continue
            if req.preload is not None:
                # PD handoff: prompt KV computed by a prefill replica
                self._inject_kv(slot, req.preload["k"], req.preload["v"],
                                plen)
                ids = self._draw_first(req, req.preload["logits"], plen)
                with self._phases("prefill_fetch", step=self._turn_step()):
                    tok = self._fetch(ids).item()
                req.preload = None  # free the host copy
            elif matched > 0:
                # radix hit: the adopted blocks already hold the prefix
                # KV — prefill ONLY the uncached suffix (TTFT tracks new
                # tokens, not prompt length). Rides the chunked-prefill
                # machinery so a long suffix still interleaves with the
                # other slots' decode.
                self._seat(slot, req, 0)
                self._prefilling[slot] = {"req": req,
                                          "tokens": full_prompt,
                                          "pos": matched}
                continue
            elif (self.prefill_chunk is not None
                  and plen > self.prefill_chunk):
                # chunked prefill: register and let the engine loop
                # advance one chunk per iteration interleaved with other
                # slots' decode; the slot starts decoding after the last
                # chunk (see _advance_chunked_prefill)
                self._seat(slot, req, 0)
                self._prefilling[slot] = {"req": req,
                                          "tokens": full_prompt, "pos": 0}
                continue
            else:
                # cap padding at max_seq: a prompt that fits must be admitted
                P = self._prompt_pad(plen)
                tokens = np.zeros((1, P), np.int32)
                tokens[0, :plen] = full_prompt
                self._count_prefill(plen, P)
                step = self._turn_step()
                with self._phases("prefill", pad_len=P, prompt_len=plen,
                                  slot=slot, step=step):
                    self._cache, logits = self._prefill(
                        self._cache, self._alloc.table_rows(slot),
                        jnp.asarray(tokens), plen, slot)
                    ids = self._draw_first(req, logits, plen)
                    counters = self._take_counters()
                    with self._phases("prefill_fetch", step=step):
                        tok = self._fetch(ids, counters,
                                          prefill=True).item()
                if self._radix is not None:
                    self._radix_insert(req, full_prompt, slot)
            self._seat(slot, req, plen)
            self._first_token(slot, tok, full_prompt)

    def _admit_block(self, slot: int, req: _Request,
                     full_prompt: List[int]) -> None:
        """Seat a request of a model that generates by blocks: the
        prompt's whole blocks through the prefill (none: the prefill of
        an empty prompt, which sets the slot's length), its tail as the
        decided positions of the slot's first block, which the next
        dispatch uploads. No token comes of it."""
        import jax.numpy as jnp

        B = self._step_rows
        plen = len(full_prompt)
        tail = plen % B
        whole = plen - tail
        P = self._prompt_pad(whole)
        tokens = np.zeros((1, P), np.int32)
        tokens[0, :whole] = full_prompt[:whole]
        self._count_prefill(whole, P)
        step = self._turn_step()
        with self._phases("prefill", pad_len=P, prompt_len=whole,
                          slot=slot, step=step):
            self._cache, _ = self._prefill(
                self._cache, self._alloc.table_rows(slot),
                jnp.asarray(tokens), whole, slot)
            counters = self._take_counters()
            if counters is not None:
                with self._phases("prefill_fetch", step=step):
                    self._fetch(None, counters, prefill=True)
        self._seat(slot, req, whole)
        self._blk_ids[slot] = 0
        self._blk_ids[slot, :tail] = full_prompt[whole:]
        self._blk_decided[slot] = np.arange(B) < tail
        self._blk_seated[slot] = True
        self._blk_undecided[slot] = B - tail
        self._blk_quota[slot] = self.config.step_quota(B - tail)
        self._blk_skip[slot] = tail

    def _turn_step(self) -> int:
        """The number a prefill's spans carry: the decode step in flight
        (the one ``decode_dispatch`` carried this turn), which the
        prefill runs behind on the device; with none in flight, the
        number the next one will carry."""
        return self._steps if self._flight is None else self._flight.step

    def _count_prefill(self, prompt_len: int, padded_len: int) -> None:
        """One more blocking prefill: ``stats()["admissions"]``."""
        adm = self._admissions
        adm["prefills"] += 1
        adm["prompt_tokens"] += prompt_len
        adm["padded_tokens"] += padded_len
        adm["also_waiting"] += len(self._waiting)

    def _seat(self, slot: int, req: _Request, cached: int) -> None:
        """``req`` takes ``slot`` with ``cached`` tokens of it in the KV
        cache."""
        self._slots[slot] = req
        self._slot_len[slot] = cached
        self._admit_counter += 1
        self._admit_seq[slot] = self._admit_counter
        self._draw_temp[slot] = req.temperature
        self._draw_request[slot] = req.number
        self._draw_rows = None

    def _first_token(self, slot: int, tok: int, cached: List[int]) -> None:
        """The occupant of ``slot`` has its prompt (``cached``) in the KV
        cache and ``tok`` from its last row: it decodes from the next
        step on, which takes ``tok`` from the host."""
        req = self._slots[slot]
        now = time.monotonic()
        req.land([tok], now)
        if req.first_token_at is None:
            req.first_token_at = now
        self._last_token[slot] = tok
        if self._proposer is not None:
            self._proposer.admit(slot, cached)
        self._maybe_finish(slot)

    def _draw_first(self, req: _Request, logits, position: int):
        """Dispatch the pick of a request's first token from the logits
        of its prompt's last row: one int32, still on the device. As in
        a turn, a greedy request's is the argmax program's: a
        deployment that never sees a temperature never builds the other."""
        if req.temperature <= 0.0:
            return self._greedy_ids(logits)
        self._sampled_tokens += 1
        return self._sample_ids(
            logits, np.array([req.temperature], np.float32), self._key,
            np.array([req.number], np.int32),
            np.array([position], np.int32))

    def _spec_decode_step(self, active: np.ndarray) -> bool:
        """One speculative iteration for ALL active slots: collect
        per-slot proposals, score every window in one batched verify,
        apply the acceptance rule per slot, and install the accepted
        lengths (target + proposer rollback). Slots with no proposal —
        lookup miss, per-request opt-out, window out of room — ride the
        same program as 1-token windows, i.e. a plain decode step.

        Returns False WITHOUT touching the cache when no slot has any
        proposal at all: every window would be 1 token, and the plain
        decode program is ~(k+1)x cheaper than the verify for the same
        result — the caller falls through to it. (Safe for the draft
        proposer too: empty proposals mean it ran zero decode steps, so
        there is nothing to roll back.)"""
        import jax.numpy as jnp

        from ray_tpu.models.speculation import (accept_greedy,
                                                accept_speculative)

        C = self.spec_k + 1
        infos: Dict[int, dict] = {}
        for slot in range(self.num_slots):
            if not active[slot]:
                continue
            req = self._slots[slot]
            start = int(self._slot_len[slot])
            k_req = self.spec_k
            if req.spec is not None:
                if not req.spec["enabled"]:
                    k_req = 0
                elif req.spec["k"] is not None:
                    k_req = min(req.spec["k"], self.spec_k)
            room = req.max_tokens - len(req.output)
            k_eff = max(0, min(k_req, room - 1,
                               self.max_seq - start - 1))
            infos[slot] = {"seq": req.prompt + req.output,
                           "target_len": start, "k": k_eff}
        with self._phases("spec_propose"):
            proposals = self._proposer.propose(infos) if infos else {}
        if not any(proposals.get(slot) for slot in infos):
            return False
        buf = np.zeros((self.num_slots, C), np.int32)
        true_lens = np.zeros(self.num_slots, np.int32)
        starts = np.zeros(self.num_slots, np.int32)
        for slot, info in infos.items():
            props = proposals.get(slot) or []
            buf[slot, 0] = self._last_token[slot]
            buf[slot, 1:1 + len(props)] = props
            true_lens[slot] = 1 + len(props)
            starts[slot] = info["target_len"]
        with self._phases("spec_verify", k=self.spec_k):
            self._cache, all_logits = self._spec_verify(
                self._cache, jnp.asarray(buf), true_lens, starts)
            # greedy slots need only the (B, C) argmax ids — ship the full
            # (B, C, vocab) logits off-device only when some slot samples
            # (a real vocab makes the difference ~(k+1)x the decode path's
            # per-step transfer)
            argmax_ids = self._spec_argmax(all_logits)
            need_full = any(self._slots[s].temperature > 0.0
                            for s in infos)
            with self._phases("spec_fetch", step=self._steps):
                greedy_np = self._fetch(argmax_ids)
                logits_np = self._fetch(all_logits) if need_full else None
        # the acceptance rule's draws are the host's, one generator a
        # verify round, seeded by the step count after its increment
        self._steps += 1
        self._turns_drained += 1
        rng = np.random.default_rng(self._steps)
        landed = time.monotonic()
        accepted_map: Dict[int, int] = {}
        touched = np.zeros(self.num_slots, bool)
        new_lens = np.zeros(self.num_slots, np.int32)
        for slot in sorted(infos):
            req = self._slots[slot]
            props = proposals.get(slot) or []
            if req.temperature <= 0.0:
                emitted, accepted = accept_greedy(
                    greedy_np[slot, :1 + len(props)], props)
            else:
                emitted, accepted = accept_speculative(
                    logits_np[slot, :1 + len(props)], props,
                    req.temperature, rng)
            self._spec_proposed += len(props)
            self._spec_accepted += accepted
            accepted_map[slot] = accepted
            # respect max_tokens and eos inside the speculative window
            room = req.max_tokens - len(req.output)
            emitted = emitted[:max(1, room)]
            if req.eos_token is not None and req.eos_token in emitted:
                emitted = emitted[:emitted.index(req.eos_token) + 1]
            req.land(emitted, landed)
            self._last_token[slot] = emitted[-1]
            # the last emitted token is pending (not yet cached), so the
            # accepted cache length is start + len(emitted); rejected
            # rows beyond it are invisible and get overwritten later
            new_len = int(starts[slot]) + len(emitted)
            self._slot_len[slot] = new_len
            touched[slot] = True
            new_lens[slot] = new_len
            self._tokens_generated += len(emitted)
        with self._phases("spec_install"):
            if touched.any():
                self._cache["length"] = self._spec_fix_len(
                    self._cache["length"], jnp.asarray(new_lens),
                    jnp.asarray(touched))
            self._proposer.after_verify(accepted_map)
        for slot in sorted(accepted_map):
            self._maybe_finish(slot)
        return True

    def _advance_chunked_prefill(self):
        """Run ONE chunk of the oldest in-progress chunked prefill; on
        the final chunk, sample the first token and activate the slot."""
        import jax.numpy as jnp

        slot = next(iter(self._prefilling))
        st = self._prefilling[slot]
        toks, pos, C = st["tokens"], st["pos"], self.prefill_chunk
        if C is None:
            # radix-suffix prefill on an engine without chunked prefill:
            # one call covering the whole uncached suffix
            C = self._prompt_pad(len(toks) - pos)
        n = min(C, len(toks) - pos)
        buf = np.zeros((1, C), np.int32)
        buf[0, :n] = toks[pos:pos + n]
        step = self._turn_step()
        with self._phases("prefill_chunk", pad_len=C, slot=slot, step=step):
            self._cache, logits = self._chunk_prefill(
                self._cache, self._alloc.table_rows(slot), jnp.asarray(buf),
                n, pos, slot)
            self._chunks_run += 1
            st["pos"] = pos + n
            if st["pos"] < len(toks):
                return
            req, plen = st["req"], len(toks)
            ids = self._draw_first(req, logits, plen)
            with self._phases("prefill_fetch", step=step):
                tok = self._fetch(ids).item()
        del self._prefilling[slot]
        if self._radix is not None:
            self._radix_insert(req, toks, slot)
        self._slot_len[slot] = plen
        self._first_token(slot, tok, toks)

    def _take_counters(self):
        """The counters the program dispatched last left in the cache,
        taken out of it: the cache is donated to the next program, and a
        step's counters are read after that one is dispatched."""
        if not self._counter_names:
            return None
        counters = self._cache["counters"]
        self._cache = dict(self._cache, counters=None)
        return counters

    def _fetch(self, ids, counters=None, prefill: bool = False
               ) -> np.ndarray:
        """A program's token ids on the host and, in the same transfer,
        the model's ``counters`` of that program (no further sync: they
        are outputs of it). The engine thread's one blocking read of the
        device: every call of it sits in a phase named ``*_fetch``
        (tests/test_llm_phases.py walks the class and holds that)."""
        if counters is None:
            return np.asarray(ids)
        import jax

        ids_np, counters = jax.device_get((ids, counters))
        if prefill:
            self._model_counters_prefill += counters
        else:
            self._model_counters += counters
        return ids_np

    def _maybe_finish(self, slot: int):
        req = self._slots[slot]
        if req is None:
            return
        done = (req.cancelled
                or len(req.output) >= req.max_tokens
                or (req.eos_token is not None and req.output
                    and req.output[-1] == req.eos_token)
                or len(req.prompt) + len(req.output) >= self.max_seq)
        if done:
            if self._radix is not None and not req.cancelled:
                # on completion, offer the whole cached sequence (prompt
                # + generated) to the tree: multi-turn conversations hit
                # on their own history. Zero-copy — the tree increfs the
                # blocks release() is about to drop its slot ref on.
                seq = (req.prompt + req.output)[:int(self._slot_len[slot])]
                self._radix_insert(req, seq, slot)
            self._record_finish(req, "cancelled" if req.cancelled else "ok")
            self._slots[slot] = None
            try:
                if self._proposer is not None:
                    self._proposer.release(slot)
                self._alloc.release(slot)
            finally:
                # last: a caller that wakes finds its slot and blocks back
                req.end()

    def _record_finish(self, req: _Request, status: str) -> None:
        """One row for a request that finished, failed or was cancelled,
        in the ring that stats() shows; and, where its caller traces,
        its queue / prefill / decode spans for the operator's timeline
        (``ray_tpu.timeline()``), from the same timestamps."""
        now = req.finished_at = time.monotonic()
        req.record = rec = [
            req.enqueued_at, req.admitted_at, req.first_token_at, None, now,
            len(req.prompt), len(req.output), req.preemptions, status]
        # read after the row is published: a poll() that sets it between
        # the two lines writes the row itself
        rec[3] = req.first_picked_at
        self._recent.append(rec)
        self._finished += 1
        if req.trace_ctx is None:
            return
        wall = time.time() - now        # monotonic -> the spans' clock
        marks = [req.enqueued_at, req.admitted_at, req.first_token_at, now]
        attrs = {"prompt_len": rec[5], "output_len": rec[6],
                 "preemptions": rec[7], "status": status}
        for name, t0, t1 in zip(("llm.queue", "llm.prefill", "llm.decode"),
                                marks, marks[1:]):
            if t0 is not None:
                tracing.record(name, wall + t0,
                               wall + (now if t1 is None else t1),
                               req.trace_ctx, attrs)

    def _preempt(self, slot: int):
        """Recompute preemption: free the slot's blocks and put the
        request back at the HEAD of the admission queue; it resumes by
        prefilling prompt+generated-so-far (vLLM's recompute mode)."""
        req = self._slots[slot]
        self._slots[slot] = None
        self._alloc.release(slot)
        if self._proposer is not None:
            self._proposer.release(slot)
        # a mid-chunked-prefill victim restarts its prefill on re-admission
        self._prefilling.pop(slot, None)
        self._waiting.appendleft(req)
        self._preemptions += 1
        req.preemptions += 1

    def _runs_next(self, slot: int) -> bool:
        """Whether ``slot`` takes part in the next decode step, by what
        the host knows without the ids in flight: its occupant decodes,
        is not cancelled, and the token it has coming from the step in
        flight is not its last by ``max_tokens`` or ``max_seq``. (An
        ``eos_token`` cannot be foreseen: such an occupant runs one step
        past its end, and :meth:`_land` drops that id.)"""
        req = self._slots[slot]
        if req is None or req.cancelled or slot in self._prefilling:
            return False
        n = len(req.output)
        if self._in_flight(slot):
            # one token of a decode step, a block step's by its counts
            coming = self._flight.coming
            n += 1 if coming is None else int(coming[slot])
        return n < req.max_tokens and len(req.prompt) + n < self.max_seq

    def _in_flight(self, slot: int) -> bool:
        """Whether the occupant of ``slot`` has a token coming from a
        step the host has not read."""
        req = self._slots[slot]
        return (req is not None and self._flight is not None
                and self._flight.reqs[slot] is req)

    def _grow_active_slots(self) -> None:
        """Before a decode step each slot that runs in it needs its next
        token's block (before a block step: its block's rows', which lie
        in one block of the pool). On pool exhaustion, preempt the
        youngest other active slot; a slot alone in the pool preempts
        itself. A victim resumes from ``prompt + output``, so the step in
        flight lands first, and what it finishes gives its blocks back."""
        bs = self._page.block_size
        for slot in range(self.num_slots):
            # the next token starts a block only at a block's multiple;
            # otherwise the blocks that cover the cached tokens cover it
            if self._slot_len[slot] % bs or not self._runs_next(slot):
                continue
            while not self._alloc.ensure(
                    slot, int(self._slot_len[slot]) + self._step_rows):
                # pool pressure order: evict cold cached prefixes (LRU,
                # refcount-0-only — a block any live slot references is
                # untouchable) BEFORE preempting a running request
                if self._radix is not None and self._radix.evict_for(1):
                    continue
                if self._flight is not None:
                    self._land()
                    if self._runs_next(slot):
                        continue
                    break
                victims = [s for s in range(self.num_slots)
                           if s != slot and self._slots[s] is not None]
                if victims:
                    victim = max(victims, key=lambda s: self._admit_seq[s])
                else:
                    victim = slot
                self._preempt(victim)
                if victim == slot:
                    break

    def _loop(self):
        import logging
        import traceback

        while not self._stop.is_set():
            try:
                with self._phases.step(
                        "turn", self._steps,
                        active=sum(r is not None for r in self._slots)):
                    self._loop_once()
            except Exception as e:  # noqa: BLE001 — engine must survive
                logging.getLogger(__name__).error(
                    "engine step failed:\n%s", traceback.format_exc())
                self._land_quietly()
                # fail every active request rather than hanging them
                for slot in range(self.num_slots):
                    req = self._slots[slot]
                    if req is not None:
                        req.error = f"engine step failed: {e!r}"
                        self._record_finish(req, "error")
                        req.end()
                        self._slots[slot] = None
                        # blocks would otherwise leak for good: only
                        # _maybe_finish/_preempt release them
                        self._alloc.release(slot)
                self._prefilling.clear()
        self._land_quietly()

    def _land_quietly(self) -> None:
        """Outside a turn (the loop's end, a failed turn): what the step
        in flight answered still goes to its requests, if it can be had."""
        import logging
        import traceback

        try:
            self._land()
        except Exception:  # noqa: BLE001 — the step went with the failure
            logging.getLogger(__name__).error(
                "the decode step in flight was lost:\n%s",
                traceback.format_exc())

    _PENDING_TTL_S = 180.0

    def _sweep_pending(self):
        """Drop submit/poll entries whose client stopped polling (stream
        abandoned mid-generation) so replicas don't leak per-request state."""
        now = time.monotonic()
        with self._pending_lock:
            stale = [rid for rid, ent in self._pending.items()
                     if now - ent.get("last_poll",
                                      ent["req"].enqueued_at)
                     > self._PENDING_TTL_S]
            for rid in stale:
                del self._pending[rid]

    def _loop_once(self):
        """One turn of the engine loop: a decode step goes to the device
        (:meth:`_decode_turn`), and while it runs there the host reads
        the step before it, gives back, grows and admits for the step
        after it. Every device dispatch and every blocking fetch of a
        turn sits in a named phase (PERF.md section 3 lists them): a span
        in a profiler capture, a row of counters in ``stats()["phases"]``."""
        phase = self._phases

        self._steps_since_sweep = getattr(self, "_steps_since_sweep", 0) + 1
        if self._steps_since_sweep >= 500:
            self._steps_since_sweep = 0
            self._sweep_pending()
        ran = self._decode_turn()
        # grow BEFORE admitting: otherwise a tight pool admits the queue
        # head (paying its prefill), then immediately preempts it as the
        # youngest slot to feed an older slot's growth — prefill thrash
        # before growing: what a window has passed goes back to its pool,
        # so the block the next token needs is there
        with phase("window_free"):
            for slot in range(self.num_slots):
                if self._runs_next(slot):
                    self._window_blocks_freed += self._alloc.trim(
                        slot, int(self._slot_len[slot]) + self._step_rows)
        with phase("grow"):
            self._grow_active_slots()
        with phase("admit",
                   waiting=self._queue.qsize() + len(self._waiting)):
            prefills = self._admissions["prefills"]
            self._admit()
            if self._admissions["prefills"] > prefills:
                self._admissions["turns_admitting"] += 1
        # one prefill chunk per iteration: bounded interference with the
        # decode of already-active slots (vLLM-class chunked prefill)
        if self._prefilling:
            self._advance_chunked_prefill()
        elif not ran and all(r is None for r in self._slots):
            with phase("idle_wait"):
                time.sleep(0.002)

    def _decode_turn(self) -> bool:
        """Give the device its next decode step, THEN read the one it
        was given before: step N+1 takes from step N only the ids that
        step left on the device, and everything else of it the host
        knows without them (lengths advance at dispatch, tokens are
        appended at the fetch), so the fetch's round trip and the
        bookkeeping of step N run under step N+1. Returns whether a
        step was dispatched or read.

        A proposer reads the host's token history, so an engine with
        one reads each step before it builds the next."""
        for slot, req in enumerate(self._slots):
            # it runs in no step again, and nothing of it is in flight:
            # no bookkeeping would meet it (a prefill sees to its own)
            if req is not None and req.cancelled \
                    and slot not in self._prefilling \
                    and not self._in_flight(slot):
                self._maybe_finish(slot)
        active = np.array([self._runs_next(s)
                           for s in range(self.num_slots)])
        if not active.any():
            landed = self._flight is not None
            self._land()
            return landed
        # speculation replaces the decode step wholesale: every active
        # slot gets a verify window (1-token windows for slots without
        # proposals), per-slot under continuous batching —
        # mid-chunked-prefill slots stay masked out. When NO slot has a
        # proposal this iteration, the plain (cheaper) decode program
        # runs instead.
        if self._proposer is not None and self._spec_decode_step(active):
            return True
        ahead = (self._dispatch(active) if self._block_step is None
                 else self._dispatch_block(active))
        self._land()
        self._flight = ahead
        if self._proposer is not None:
            self._land()
        return True

    def _dispatch(self, active: np.ndarray) -> _Flight:
        """One decode step of the ``active`` slots and the program that
        takes its tokens, both left on the device."""
        import jax.numpy as jnp

        before = self._flight
        step = self._steps + (before is not None)
        with self._phases("decode_dispatch", step=step):
            reqs = [r if a else None for r, a in zip(self._slots, active)]
            if before is None:
                self._turns_drained += 1
                # of a copy (asarray may alias the host's buffer): the
                # host writes a seated slot's token while this step runs
                tokens = jnp.asarray(self._last_token.copy())
            else:
                self._turns_overlapped += 1
                tokens = before.ids
                seated = np.array([r is not None and r is not b
                                   for r, b in zip(reqs, before.reqs)])
                if seated.any():
                    tokens = self._merge_ids(
                        jnp.asarray(np.where(seated, self._last_token, -1)),
                        tokens)
            self._cache, logits = self._decode(
                self._cache, self._alloc.device_tables(), tokens,
                jnp.asarray(active))
            counters = self._take_counters()
            # what the turn needs, from what it holds: the all-greedy
            # turn's program is the argmax alone
            sampled = int(np.count_nonzero(self._draw_temp[active] > 0.0))
            if not sampled:
                self._greedy_turns += 1
                ids = self._greedy_ids(logits)
            else:
                self._sampled_turns += 1
                self._sampled_tokens += sampled
                if self._draw_rows is None:
                    # of copies too: _seat writes the rows under this step
                    self._draw_rows = (jnp.asarray(self._draw_temp.copy()),
                                       jnp.asarray(self._draw_request.copy()))
                temperature, request = self._draw_rows
                # a slot's next token stands after its cached tokens and
                # the one this step feeds: the turn's one upload of its own
                ids = self._sample_ids(
                    logits, temperature, self._key, request,
                    jnp.asarray((self._slot_len + 1).astype(np.int32)))
            self._slot_len[active] += 1
        return _Flight(step, ids, counters, reqs, sampled)

    def _land(self) -> None:
        """Read the ids of the step in flight, if there is one, and do
        its bookkeeping: a token a slot, and the finishes they bring."""
        flight, self._flight = self._flight, None
        if flight is None:
            return
        if flight.coming is not None:
            return self._land_block(flight)
        with self._phases("logits_fetch", step=flight.step):
            ids = self._fetch(flight.ids, flight.counters)
        self._steps += 1
        ran = [slot for slot, r in enumerate(flight.reqs) if r is not None]
        landed = time.monotonic()
        # ONE span around the slots' loop, never one per slot; the
        # tokens are picked already, what is left is bookkeeping
        with self._phases("sample", active=len(ran),
                          sampled=flight.sampled):
            for slot in ran:
                req = flight.reqs[slot]
                if self._slots[slot] is not req:
                    # it ended (an EOS, a cancel) with the token of the
                    # step before this one, which ran before the host
                    # had read that token
                    self._surplus_dropped += 1
                    continue
                tok = ids[slot]
                req.land([int(tok)], landed)
                self._last_token[slot] = tok
                self._tokens_generated += 1
                self._maybe_finish(slot)

    # ---------------------------------------------------- the block turn
    def _dispatch_block(self, active: np.ndarray) -> _Flight:
        """One block step of the ``active`` slots and the program that
        decides positions, both left on the device. What the step does
        to a slot follows from the host's counts: a slot with nothing
        undecided commits (its length grows by a block here, its tokens
        are appended at the fetch, its next block begins), any other
        decides its quota."""
        import jax.numpy as jnp

        B = self._step_rows
        before = self._flight
        step = self._steps + (before is not None)
        with self._phases("block_dispatch", step=step):
            reqs = [r if a else None for r, a in zip(self._slots, active)]
            if before is None:
                self._turns_drained += 1
            else:
                self._turns_overlapped += 1
            if self._blk_seated.any():
                # of copies (asarray may alias the host's buffers): the
                # host seats the next request while this step runs
                self._block_state = self._seat_blocks(
                    *self._block_state, jnp.asarray(self._blk_ids.copy()),
                    jnp.asarray(self._blk_decided.copy()),
                    jnp.asarray(self._blk_seated.copy()))
                self._blk_seated[:] = False
            ids, decided = self._block_state
            commits = active & (self._blk_undecided == 0)
            denoises = active & ~commits
            quota = np.where(denoises, np.minimum(self._blk_quota,
                                                  self._blk_undecided), 0)
            self._cache, logits = self._block_step(
                self._cache, self._alloc.device_tables(), ids, decided,
                jnp.asarray(active))
            counters = self._take_counters()
            sampled = int(np.count_nonzero(self._draw_temp[denoises] > 0.0))
            draw = None
            if not sampled:
                self._greedy_turns += 1
            else:
                self._sampled_turns += 1
                self._sampled_tokens += int(
                    quota[self._draw_temp > 0.0].sum())
                if self._draw_rows is None:
                    self._draw_rows = (jnp.asarray(self._draw_temp.copy()),
                                       jnp.asarray(self._draw_request.copy()))
                temperature, request = self._draw_rows
                draw = (temperature, self._key, request,
                        jnp.asarray(self._slot_len.astype(np.int32)))
            ids, decided, out = self._block_decide(
                logits, ids, decided, jnp.asarray(quota.astype(np.int32)),
                draw)
            self._block_state = (ids, decided)
            coming = np.where(commits, B - self._blk_skip, 0)
            self._slot_len[commits] += B
            self._blk_undecided -= quota
            self._blk_undecided[commits] = B
            self._blk_quota[commits] = self.config.step_quota(B)
            self._blk_skip[commits] = 0
            n_commits = int(np.count_nonzero(commits))
            counts = self._block_counts
            counts["block_steps"] += 1
            counts["slot_steps"] += int(np.count_nonzero(active))
            counts["commit_steps"] += n_commits
            counts["blocks_committed"] += n_commits
            counts["positions_decided"] += int(quota.sum())
        return _Flight(step, out, counters, reqs, sampled, coming)

    def _land_block(self, flight: _Flight) -> None:
        """Read what the block step in flight committed (a block's ids
        a slot that committed; nothing of the others) and do its
        bookkeeping: the tokens, cut at ``max_tokens`` or after an EOS,
        and the finishes they bring."""
        with self._phases("block_fetch", step=flight.step):
            ids = self._fetch(flight.ids, flight.counters)
        self._steps += 1
        B = self._step_rows
        committed = np.nonzero(flight.coming)[0]
        landed = time.monotonic()
        # ONE span around the slots that committed, never one per slot
        # (a slot that only decided has nothing to book)
        with self._phases("block_commit", active=len(committed),
                          sampled=flight.sampled):
            for slot in committed:
                req = flight.reqs[slot]
                if self._slots[slot] is not req:
                    # it ended (an EOS, a cancel) with the block before
                    self._surplus_dropped += 1
                    continue
                toks = ids[slot, B - flight.coming[slot]:].tolist()
                toks = toks[:req.max_tokens - len(req.output)]
                if req.eos_token is not None and req.eos_token in toks:
                    toks = toks[:toks.index(req.eos_token) + 1]
                req.land(toks, landed)
                if req.first_token_at is None:
                    req.first_token_at = landed
                self._tokens_generated += len(toks)
                self._maybe_finish(slot)


class LLMServer:
    """Serve deployment wrapper: one engine per replica.

    Deploy with ``serve.deployment(LLMServer).options(
    ray_actor_options={"num_tpus": N})``; requests are token-id lists
    (tokenization is a host-side pre/post step, kept off the replica).
    """

    def __init__(self, model: str = "tiny", num_slots: int = 8,
                 max_seq: Optional[int] = None, **engine_kwargs):
        self.engine = LLMEngine(model=model, num_slots=num_slots,
                                max_seq=max_seq, **engine_kwargs)

    @staticmethod
    def _parse(prompt_or_request, kwargs: Dict[str, Any]):
        """Accept either direct args (handle calls) or a proxy Request whose
        JSON body is {"prompt": [...], "max_tokens": n, ...}."""
        from ray_tpu.serve.proxy import Request

        if isinstance(prompt_or_request, Request):
            body = prompt_or_request.json() or {}
            merged = {"max_tokens": body.get("max_tokens", 64),
                      "temperature": body.get("temperature", 0.0),
                      "eos_token": body.get("eos_token"),
                      "speculation": body.get("speculation"),
                      # tenant identity for engine-level fair share:
                      # body field wins, else the same x-client-id
                      # header the proxy's admission control keys on
                      "tenant": (body.get("tenant")
                                 or prompt_or_request.headers.get(
                                     "x-client-id"))}
            return body.get("prompt", []), merged
        return prompt_or_request, kwargs

    def __call__(self, prompt_or_request, **kwargs) -> List[int]:
        prompt, kw = self._parse(prompt_or_request, kwargs)
        return self.engine.generate(
            prompt, kw.get("max_tokens", 64), kw.get("temperature", 0.0),
            kw.get("eos_token"), speculation=kw.get("speculation"),
            tenant=kw.get("tenant"))

    def submit(self, prompt_or_request, **kwargs) -> str:
        prompt, kw = self._parse(prompt_or_request, kwargs)
        return self.engine.submit(
            prompt, kw.get("max_tokens", 64), kw.get("temperature", 0.0),
            kw.get("eos_token"), speculation=kw.get("speculation"),
            tenant=kw.get("tenant"))

    def poll(self, request_id: str) -> Dict[str, Any]:
        return self.engine.poll(request_id)

    def cancel(self, request_id: str) -> bool:
        return self.engine.cancel(request_id)

    def prefix_digest(self) -> List[int]:
        """Exported through the Replica harness → controller →
        router-refresh path so prefix-aware handles can route to the
        replica holding the longest cached prefix."""
        return self.engine.prefix_digest()

    # the longest a stream goes without looking at its request: the net
    # under the wake-up. It hangs below any decode turn or prefill that
    # is served (10-70 ms a program), so between two landings it does
    # not fire: at the old poll's 5 ms it fired twice a 10 ms turn, each
    # time an empty poll, and no token came sooner for it
    _STREAM_WAIT_S = 0.1

    def stream(self, prompt_or_request, **kwargs):
        """Generator-protocol streaming (round 11): tokens yield as the
        engine produces them, and the proxy's SSE path PUSHES each one to
        the client over the streaming-generator protocol — no proxy→
        replica poll RPCs.  The wait on the engine is replica-local (this
        generator runs on the replica's executor thread, never an event
        loop) and is a wake-up, not a poll period: the engine thread
        writes a landed token's time, then the token, then sets the
        request's event (``_Request.land``; the same event at its end,
        its failure and its cancel), and this loop waits on the event,
        clears it and polls, in that order, so a token that lands
        between a poll and the next wait ends that wait at once.
        ``_STREAM_WAIT_S`` bounds a wait whatever the engine does.
        ``submit``/``poll`` stay for pre-generator callers."""
        prompt, kw = self._parse(prompt_or_request, kwargs)
        request_id = self.engine.submit(
            prompt, kw.get("max_tokens", 64), kw.get("temperature", 0.0),
            kw.get("eos_token"), speculation=kw.get("speculation"),
            tenant=kw.get("tenant"))
        from ray_tpu.serve.proxy import SSEBatch

        while True:
            self.engine.wait_fresh(request_id, self._STREAM_WAIT_S)
            st = self.engine.poll(request_id)
            chunks = st["chunks"]
            if len(chunks) == 1:
                yield chunks[0]
            elif chunks:
                # burst since the last engine poll: ONE streamed item (one
                # report RPC), fanned back out to per-token SSE events at
                # the proxy — per-token report RPCs were slower than the
                # old poll loop
                yield SSEBatch(chunks)
            if st["done"]:
                return

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()
