"""Counts of JAX backend compiles and persistent-cache hits, from
``jax.monitoring`` events (the listener of the repository's chip smoke
check, kept here so the benchmark's count cannot move with the program).
A program served from the persistent cache still reports a backend
compile event, at the time it is read. Each event is kept with its time
and the name of the program, so a compile inside a window can be named."""
from __future__ import annotations

import threading
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Running totals since :meth:`install`; read them with
    :meth:`snapshot` at the two edges of a window."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.events: list = []          # (monotonic end time, name, secs)

    def _on_duration(self, event, secs, fun_name="?", **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.compiles += 1
                self.compile_s += secs
                self.events.append((time.monotonic(), fun_name, secs))

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def install(self) -> "CompileLog":
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def uninstall(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles, "compile_s": self.compile_s,
                    "cache_hits": self.cache_hits}

    def between(self, t0: float, t1: float) -> list:
        """[(seconds after t0, program name, compile seconds)] of the
        compiles that ended in [t0, t1)."""
        with self._lock:
            return [(t - t0, name, secs) for t, name, secs in self.events
                    if t0 <= t < t1]
