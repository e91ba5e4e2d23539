"""Compilations and persistent-cache loads, counted through JAX's own
monitoring events.  A program loaded from the persistent cache fires the
backend-compile event too (with the load's duration), so ``compiles``
counts both.

Copied from ``chip_smoke.py`` (``_listen`` and ``compile_window``), with
counts of compile events kept beside the seconds.
"""

from __future__ import annotations

import jax

COUNTS = {"compile_s": 0.0, "compiles": 0, "cache_hits": 0, "cache_misses": 0}
_listening = False


def listen() -> None:
    """Register the listeners once per process."""
    global _listening
    if _listening:
        return

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            COUNTS["compile_s"] += secs
            COUNTS["compiles"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            COUNTS["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            COUNTS["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _listening = True


class compile_window:
    """Compile seconds, compiles and cache hits/misses inside a ``with``
    block (``stats`` after exit)."""

    def __enter__(self):
        listen()
        self.start = dict(COUNTS)
        return self

    def __exit__(self, *exc):
        self.stats = {k: COUNTS[k] - self.start[k] for k in COUNTS}
        return False
