"""What the serving engine (:class:`ray_tpu.serve.llm.LLMEngine`) asks
of a model: one small interface, so that a decoder whose layers, caches
and programs are not the dense one's is served by the same loop.

A serving model has

    config            with ``vocab_size`` and ``max_seq``
    lacks             the engine mechanisms it has no builders for, out
                      of ``MECHANISMS``; asking for one raises at
                      construction, naming it
    init_params(key)  weights, where the caller brings none
    paged(params, *, num_slots, max_seq, block_size, pool_tokens)
                      -> :class:`PagedPrograms`

The dense decoder (:class:`DenseDecoder` around a ``LlamaConfig``) gives
the builders of :mod:`ray_tpu.models.paged_cache` as they are; its other
mechanisms (slot cache, speculation, prefix caches, chunked prefill, KV
transfer) stay in the engine, built from its ``LlamaConfig``. A config
class of another model names its own through ``serving_model()``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

MECHANISMS = {
    "slot_cache": "kv_cache='slot'",
    "speculation": "speculation",
    "prefix_cache": "a prefix cache (prefix_cache / prefix_cache_bytes)",
    "prefill_chunk": "chunked prefill (prefill_chunk)",
    "kv_transfer": "KV inject / extract (llm_pd, submit_prefilled)",
}


@dataclasses.dataclass
class PagedPrograms:
    """A model on the paged path. ``alloc`` is a ``BlockAllocator`` or a
    ``KVStateManager`` (one allocator for each kind of KV state);
    ``prefill(cache, alloc.table_rows(slot), tokens (1, P), true_len,
    slot)`` and ``decode(cache, alloc.device_tables(), tokens (B,),
    active (B,))`` return ``(cache, logits)``; ``page`` is the geometry
    of the state that keeps the whole sequence. ``counters`` names the
    entries of ``cache["counters"]``, which the engine fetches with the
    logits and sums in ``stats()["model_counters"]``."""

    alloc: Any
    cache: Any
    prefill: Callable
    decode: Callable
    page: Any
    inject: Optional[Callable] = None
    counters: Tuple[str, ...] = ()


class DenseDecoder:
    """The dense decoder of :mod:`ray_tpu.models.llama` behind the
    interface, by the builders it has."""

    lacks: Tuple[str, ...] = ()

    def __init__(self, config):
        self.config = config

    def init_params(self, key):
        from ray_tpu.models import llama

        return llama.init_params(self.config, key)

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


def serving_model(config):
    """The serving model of a config object: its own
    (``config.serving_model()``), or the dense decoder's."""
    own = getattr(config, "serving_model", None)
    return own() if own is not None else DenseDecoder(config)
