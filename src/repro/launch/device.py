"""Device set-up shared by the entry points: the persistent compile cache,
the device banner, and a count of the XLA compilations a run makes."""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fixed cache location inside the checkout: the cache key includes
#: the path, so it never moves between runs
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache goes to :data:`CACHE_DIR`.  Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports the devices."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def banner() -> str:
    d = device_info()
    return f"device: platform={d['platform']} kind={d['kind']} count={d['count']}"


class CompileLog:
    """Counts the executables XLA builds in this process (persistent-cache
    hits included) and the seconds they take, from JAX's own monitoring
    event.  ``mark()`` returns the totals so far; differences between two
    marks count what happened in between."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def mark(self) -> tuple[int, float]:
        return self.count, self.seconds

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
