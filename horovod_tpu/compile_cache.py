"""Persistent XLA compile cache that can be placed from outside.

The step programs of the models this framework trains take from tens of
seconds to minutes to compile, and a fresh machine starts with none of
them.  jax's persistent compilation cache stores a compiled program under
a key that includes the cache directory's own path, so a directory that
moves never hits.  Hence one rule, applied by everything that compiles a
step (``benchmark/run.py``, ``chip_smoke.py``, ``bench.py``'s workers):

* where ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own reading of it
  stands and no other directory is set here — an operator (or a machine
  image) decides where compiled programs live and how long;
* where it is not, the cache goes to ``<checkout>/.jax_cache`` — a fixed
  path beside the package, never one derived from a temp dir, a pid or
  the clock.
"""

from __future__ import annotations

import os

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_WRITE = "/jax/compilation_cache/cache_misses"   # recorded on a write


def enable() -> str:
    """Turn the persistent cache on and return its directory.  Call
    before the first compile: jax decides once per process whether the
    cache is in use."""
    import jax

    if not os.environ.get(ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # Store whatever costs a second to rebuild, whatever its size; the
    # step programs (the point of the cache) are far above both.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


class CacheEvents:
    """Counts this process's persistent-cache hits and writes from jax's
    own monitoring events, so a caller can say of one compile whether it
    was read back (``hits`` moved) or built and stored (``writes``)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _WRITE:
            self.writes += 1
