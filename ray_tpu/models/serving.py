"""What the serving engine (:class:`ray_tpu.serve.llm.LLMEngine`) asks
of a model: one small interface, so that a decoder whose layers, caches
and programs are not the dense one's is served by the same loop. Every
program the engine runs, every cache it holds and every fact of the
cache's geometry it uses comes from here; this text is the interface's
only description.

A serving model has

    config            with ``vocab_size`` and ``max_seq``
    init_params(key)  weights, where the caller brings none
    paged(params, *, num_slots, max_seq, block_size, pool_tokens)
                      -> :class:`PagedPrograms`

and ONE MORE BUILDER FOR EACH ENGINE MECHANISM IT HAS. A model without
the builder has no such attribute, and the engine refuses the mechanism
by name where it is asked for (at construction; ``submit_prefilled`` at
the call):

    slot(params, *, num_slots, max_seq) -> :class:`PagedPrograms`
                      ``kv_cache="slot"``: a cache that reserves
                      ``max_seq`` rows a slot, in the shape of the paged
                      one (:class:`SlotReservation` for an allocator)
    chunked_prefill(params, programs, chunk=None)
                      ``prefill_chunk``, and the suffix after a prefix
                      hit: ``call(cache, alloc.table_rows(slot), tokens
                      (1, C), tokens in the chunk, start, slot) ->
                      (cache, logits)`` for the cache of ``programs``;
                      ``chunk`` is the C the engine will always call it
                      with, refused here if the program cannot take it
    speculative_verify(params) -> (verify, install_lengths)
                      ``speculation``, on the slot cache:
                      ``verify(cache, tokens (B, C), true_lens (B,),
                      starts (B,)) -> (cache, logits (B, C, vocab))`` and
                      ``install_lengths(length, new, touched) -> length``
    block_copy(programs) -> ``copy(cache, src, dst) -> cache``
    block_bytes(programs) -> bytes of KV state in one block
                      a prefix cache (with ``chunked_prefill``): the copy
                      on write of a shared block, and what the cache's
                      budget is counted in
    kv_shape(tokens)  KV transfer: the ``(layers, tokens, kv_heads,
                      head_dim)`` of the k and of the v that
                      ``PagedPrograms.inject`` takes for a prompt
    block_denoise(params, programs) -> (step, decide)
                      generation by blocks IN PLACE OF the decode step
                      (``PagedPrograms.decode`` is then None): a slot
                      holds a block of ``config.block_length`` positions,
                      some decided; ``step(cache, alloc.device_tables(),
                      ids (B, L) i32, decided (B, L) bool, active (B,))
                      -> (cache, logits (B, L, vocab))`` runs every
                      active slot's block against its cached prefix,
                      feeding ``config.mask_token_id`` where a position
                      is not decided, writes the block's rows after the
                      slot's ``length`` and, for a slot whose positions
                      are ALL decided, keeps them: its length grows by
                      ``L`` (the commit). ``decide(logits, ids, decided,
                      quota (B,) i32, draw=None) -> (ids, decided, out
                      (B, L))`` decides ``quota`` more positions of each
                      slot on the device; a slot that has just committed
                      gets its block's ids in ``out`` and an undecided
                      block. The engine seats a prompt's whole blocks by
                      ``prefill`` (whose mask is the model's own) and its
                      tail as decided positions of the first block, and
                      has every step decide ``config.step_quota(n)``
                      positions of a block that began with ``n``
                      undecided (the rule is the model's; the engine
                      only counts); ``draw`` = (temperature (B,),
                      key, request (B,), length (B,)) where a slot draws
                      at a temperature

and, whether it has any of those or none, ONE OPTIONAL MEMBER:

    place(params) -> params
                      the weights as the model's programs read them on
                      this device: same tree, shapes and values, some
                      leaves in another device layout (the dense block's
                      ``wq`` / ``wk`` / ``wv``:
                      :func:`ray_tpu.models.llama.serving_layout`). The
                      engine calls it once, on a TPU, on the weights it
                      made or was given and before it builds a program,
                      and OWNS what it was given from there on: a leaf
                      that is re-laid is donated, the caller's buffer of
                      it is gone and ``engine.params`` is the tree that
                      is served, because 16 GB hold one copy of the
                      weights beside a pool, not one and a leaf's second.
                      A model without the member is served the arrays it
                      was given; off the TPU every model is
                      (``stats()["weights_relaid_bytes"]`` says how many
                      bytes were re-laid). The builders are jitted
                      without ``in_shardings``, so a program is compiled
                      for the layout its weights arrive in, whoever calls
                      the builder

The dense decoder (:class:`DenseDecoder` around a ``LlamaConfig``) has
them all, each a call of the builder in :mod:`ray_tpu.models.decoding`,
:mod:`ray_tpu.models.paged_cache` or :mod:`ray_tpu.models.speculation`.
A config class of another model names its own serving model through
``serving_model()``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Tuple


@dataclasses.dataclass
class PagedPrograms:
    """A model on the paged path. ``alloc`` is a ``BlockAllocator`` or a
    ``KVStateManager`` (one allocator for each kind of KV state);
    ``prefill(cache, alloc.table_rows(slot), tokens (1, P), true_len,
    slot)`` and ``decode(cache, alloc.device_tables(), tokens (B,),
    active (B,))`` return ``(cache, logits)``; ``inject(cache,
    alloc.table_rows(slot), k, v, true_len, slot)`` returns the cache
    with a prompt's KV rows written; ``page`` is the geometry of the
    state that keeps the whole sequence; ``pad(n)`` is the padded length
    a prompt of ``n`` tokens is given (left out: a bucket of whole blocks
    of ``page``). ``counters`` names the entries of
    ``cache["counters"]``, which the engine fetches with the logits and
    sums in ``stats()["model_counters"]``. ``decode`` is None for a
    model that brings ``block_denoise`` in its place."""

    alloc: Any
    cache: Any
    prefill: Callable
    decode: Optional[Callable]
    page: Any
    inject: Optional[Callable] = None
    counters: Tuple[str, ...] = ()
    pad: Optional[Callable[[int], int]] = None

    def __post_init__(self):
        if self.pad is None:
            from ray_tpu.models.paged_cache import prompt_bucket

            self.pad = prompt_bucket(self.page)


class SlotReservation:
    """The slot cache's allocator: the flat reservation written down in
    the paged allocators' interface. Every slot holds ``max_seq`` rows
    from the start, so a sequence that long always fits, nothing grows,
    nothing is given back, and there is no table and no block to report."""

    def __init__(self, max_seq: int):
        self.max_seq = max_seq

    def fits(self, tokens: int) -> bool:
        return tokens <= self.max_seq

    def lacking(self, tokens: int, shared: int = 0, headroom: int = 0
                ) -> int:
        return 0

    def ensure(self, slot: int, tokens: int) -> bool:
        return True

    def trim(self, slot: int, tokens: int) -> int:
        return 0

    def release(self, slot: int) -> None:
        pass

    def table_rows(self, slot: int):
        return None

    def device_tables(self):
        return None

    def free_blocks(self):
        return None

    def pools(self, slot_lengths=()) -> dict:
        return {}


def _without_table(program):
    """A slot program behind the paged call: the table is taken and
    dropped."""
    return lambda cache, _table, *args: program(cache, *args)


class DenseDecoder:
    """The dense decoder of :mod:`ray_tpu.models.llama` behind the
    interface, by the builders it has."""

    def __init__(self, config):
        self.config = config

    def init_params(self, key):
        from ray_tpu.models import llama

        return llama.init_params(self.config, key)

    def place(self, params):
        from ray_tpu.models import llama

        return llama.serving_layout(params)

    def paged(self, params, *, num_slots: int, max_seq: int,
              block_size: int, pool_tokens: int) -> PagedPrograms:
        from ray_tpu.models.paged_cache import (
            BlockAllocator, PagedConfig, init_paged_cache,
            make_paged_decode_step, make_paged_inject, make_paged_prefill)

        page = PagedConfig(num_blocks=1 + -(-pool_tokens // block_size),
                           block_size=block_size, max_seq=max_seq)  # +null
        return PagedPrograms(
            alloc=BlockAllocator(page, num_slots),
            cache=init_paged_cache(self.config, page, num_slots),
            prefill=make_paged_prefill(params, self.config, page),
            decode=make_paged_decode_step(params, self.config, page),
            page=page, inject=make_paged_inject(self.config, page))

    def slot(self, params, *, num_slots: int, max_seq: int
             ) -> PagedPrograms:
        """The dense decoder's second cache: ONE block of ``max_seq``
        rows a slot, and the slot programs, which take no table."""
        from ray_tpu.models.decoding import (
            init_cache, make_decode_step, make_inject, make_prefill,
            pad_to_bucket)
        from ray_tpu.models.paged_cache import PagedConfig

        return PagedPrograms(
            alloc=SlotReservation(max_seq),
            cache=init_cache(self.config, num_slots, max_seq),
            prefill=_without_table(make_prefill(params, self.config)),
            decode=_without_table(make_decode_step(params, self.config)),
            page=PagedConfig(num_blocks=1 + num_slots, block_size=max_seq,
                             max_seq=max_seq),
            inject=_without_table(make_inject(self.config)),
            pad=lambda n: min(pad_to_bucket(n), max_seq))

    def chunked_prefill(self, params, programs: PagedPrograms,
                        chunk: Optional[int] = None):
        if isinstance(programs.alloc, SlotReservation):
            from ray_tpu.models.decoding import make_chunked_prefill

            return _without_table(make_chunked_prefill(params, self.config))
        from ray_tpu.models.paged_cache import make_chunked_paged_prefill

        if chunk is not None and chunk % programs.page.block_size:
            raise ValueError(
                f"prefill_chunk={chunk} must be a multiple of "
                f"kv_block_size={programs.page.block_size}")
        return make_chunked_paged_prefill(params, self.config, programs.page)

    def speculative_verify(self, params):
        from ray_tpu.models.decoding import make_batched_spec_verify
        from ray_tpu.models.speculation import make_length_installer

        return (make_batched_spec_verify(params, self.config),
                make_length_installer())

    def block_copy(self, programs: PagedPrograms):
        from ray_tpu.models.paged_cache import make_block_copy

        return make_block_copy(self.config, programs.page)

    def block_bytes(self, programs: PagedPrograms) -> int:
        return (2 * programs.cache["k"].dtype.itemsize
                * math.prod(self.kv_shape(programs.page.block_size)))

    def kv_shape(self, tokens: int) -> Tuple[int, int, int, int]:
        c = self.config
        return (c.n_layers, tokens, c.n_kv_heads, c.head_dim)


def init_from_shapes(shapes, key, std: float, stds: dict, dtype):
    """Seeded weights for a tree of shapes (tuples): each leaf a normal
    draw at the standard deviation ``stds`` gives its name, else ``std``."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda t: isinstance(t, tuple))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        (jax.random.normal(k, shape, jnp.float32)
         * stds.get(path[-1].key, std)).astype(dtype)
        for k, (path, shape) in zip(keys, leaves)])


def serving_model(config=None, preset: str = "tiny"):
    """The serving model of a config object: its own
    (``config.serving_model()``), or the dense decoder's. Without a
    config: the dense decoder at the ``preset`` of that name."""
    if config is None:
        from ray_tpu.models import llama

        config = llama.CONFIGS[preset]
    own = getattr(config, "serving_model", None)
    return own() if own is not None else DenseDecoder(config)
