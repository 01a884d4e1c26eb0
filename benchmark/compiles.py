"""Counts what jax compiles, from its own log records and monitoring events
(as chip_smoke.py does): a compilation inside the measured window fails the
run, and the seconds inside the backend-compile step during set-up are the
per-layer metric `compile_s`.
"""

from __future__ import annotations

import logging

LOGGERS = ("jax._src.interpreters.pxla", "jax._src.compiler", "jax._src.dispatch")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileWatch(logging.Handler):
    def __init__(self):
        super().__init__(logging.DEBUG)
        self.programs = []         # every program jax set out to compile
        self.cache_hits = 0
        self.cache_misses = 0
        self.backend_compile_s = 0.0

    @property
    def count(self) -> int:
        return len(self.programs)

    def emit(self, record):
        msg = str(record.msg)
        if msg.startswith("Compiling %s"):
            self.programs.append(str(record.args[0]))
        elif msg.startswith("Persistent compilation cache hit"):
            self.cache_hits += 1
        elif msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.cache_misses += 1

    def counts(self) -> dict:
        return {"programs": self.count, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "backend_compile_s": self.backend_compile_s}

    def _on_duration(self, name, secs, **_):
        if name == COMPILE_EVENT:
            self.backend_compile_s += secs

    def install(self):
        """For the life of the process: a run is one process."""
        import jax

        for name in LOGGERS:
            lg = logging.getLogger(name)
            lg.setLevel(logging.DEBUG)
            lg.propagate = False
            lg.addHandler(self)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self
