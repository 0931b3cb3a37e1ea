"""JAX's persistent compilation cache, and compile counting from JAX's own
compile events (not from counters bumped inside traced bodies)."""
from __future__ import annotations

import collections
import os
from pathlib import Path

import jax

# recorded by JAX around every executable it builds for a jit cache miss,
# whether XLA compiles it or the persistent cache supplies it
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
    — a fixed path, since the cache is keyed by it."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT / ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``;
    call before the first compile.  Returns the directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Counts backend compiles while active, in total and by function name.

    >>> with CompileCounter() as cc:
    ...     run()
    >>> cc.count, cc.by_name["jit(step_fn)"]
    """

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.by_name: collections.Counter = collections.Counter()

    def _listen(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration
            self.by_name[kwargs.get("fun_name", "?")] += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)
