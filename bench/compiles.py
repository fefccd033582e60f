"""Programs JAX builds as they happen: backend compiles, and the share of
them that missed the persistent compilation cache."""
from __future__ import annotations


class CompileClock:
    """``builds`` and ``seconds``: programs JAX built for the backend, and
    the time it took (a program read back from the persistent cache counts
    too: it is built for a new shape all the same); ``misses``: those the
    persistent cache did not hold."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        import jax
        self.builds = 0
        self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_build)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_build(self, event: str, duration: float, **_) -> None:
        if event == self.BUILD:
            self.builds += 1
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == self.MISS:
            self.misses += 1
