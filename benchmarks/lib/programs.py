"""Counts the programs JAX builds, from JAX's own monitoring events.

Copied from ``chip_smoke.Programs`` (PR 23). A program read from the
persistent cache still passes through ``backend_compile_duration`` (the
duration is then the read), so ``n`` counts programs made ready, and
``hits``/``misses`` say which of them the cache served."""

from __future__ import annotations

import jax


class Programs:
    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"programs": self.n, "compile_s": self.seconds,
                "cache_hits": self.hits, "cache_misses": self.misses}
