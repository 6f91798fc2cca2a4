"""Where compiled programs are kept between processes and runs.

The directory is placed from outside: ``JAX_COMPILATION_CACHE_DIR`` if
it is set, else the fixed ``<checkout>/.jax_cache``. Never a path made
from a temp dir, a pid or the time — the path is part of the cache key,
so a directory that moves never hits.
"""

from __future__ import annotations

import os
import sys
from typing import Dict

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache(environ=os.environ, modules=sys.modules) -> str:
    """Called where a process first gets its chip (a worker's TPU grant,
    a bench script's start). Returns the directory in use."""
    path = environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path                       # placed from outside: set nothing
    path = os.path.join(_CHECKOUT, ".jax_cache")
    environ["JAX_COMPILATION_CACHE_DIR"] = path   # read when jax is imported
    if "jax" in modules:
        modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path


_counts: Dict[str, int] = {}
_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
           "/jax/compilation_cache/cache_hits": "hits"}


def compile_cache_counts() -> Dict[str, int]:
    """Live counts of this process's persistent-cache lookups and hits
    (what a replica's ``stats`` and a train loop's report carry)."""
    if not _counts:
        import jax

        _counts.update(requests=0, hits=0)

        def on_event(event, **_):
            key = _EVENTS.get(event)
            if key is not None:
                _counts[key] += 1

        jax.monitoring.register_event_listener(on_event)
    return _counts
