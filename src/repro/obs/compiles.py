"""Compile counter: every program JAX compiles or loads from its
persistent cache, as a timeline instant on the host clock.

Importing :mod:`repro.obs` registers one ``jax.monitoring`` listener.
JAX reports ``/jax/core/compile/backend_compile_duration`` once per
program handed to the backend — compiled, or loaded from the persistent
cache, whose ``/jax/compilation_cache/cache_retrieval_time_sec`` it
reports within that span.  Each lands in the default timeline as an
instant (``jax.compile`` / ``jax.cache_load``, lane ``runtime``) stamped
with ``time.perf_counter()`` when JAX reports it, carrying
the event's duration (and the program's name, where JAX gives it).  A
call that hits the in-memory cache reports nothing, so an instant inside
a steady loop names the step that recompiled.
"""

from __future__ import annotations

import time
from typing import List

import jax

from repro.obs import timeline

#: jax.monitoring event -> timeline instant
EVENTS = {
    "/jax/core/compile/backend_compile_duration": "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
}


def _on_duration(event: str, duration_s: float, **kwargs) -> None:
    name = EVENTS.get(event)
    if name is not None:
        timeline.get_timeline().instant(
            name, "runtime", time.perf_counter() * 1e6,
            duration_s=duration_s, **kwargs)


def programs(lo_s: float, hi_s: float) -> List[timeline.Event]:
    """The default timeline's ``jax.compile`` instants (programs compiled
    or loaded from the persistent cache) between two ``perf_counter``
    readings, in seconds."""
    return [e for e in timeline.get_timeline().events
            if e.name == "jax.compile"
            and lo_s * 1e6 <= e.ts_us <= hi_s * 1e6]


jax.monitoring.register_event_duration_secs_listener(_on_duration)
