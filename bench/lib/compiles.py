"""Count backend compiles with a ``jax.monitoring`` listener.

Copied from ``tools/reprolint/trace_audit.py`` (``_on_event`` /
``ensure_registered``): JAX emits one ``.../backend_compile_duration`` event
per executable built and none on a cache hit.  Kept here because ``tools/``
is program code and the yardstick must not move with it.
"""
from __future__ import annotations

import threading

_BACKEND_COMPILE_SUFFIX = "backend_compile_duration"


class CompileCounter:
    """Backend compiles seen since :meth:`install`.  jax.monitoring has no
    per-listener removal, so one counter is installed per process and left
    in place; it only increments."""

    def __init__(self) -> None:
        self.compiles = 0
        self._lock = threading.Lock()
        self._installed = False

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event.endswith(_BACKEND_COMPILE_SUFFIX):
            with self._lock:
                self.compiles += 1

    def install(self) -> "CompileCounter":
        if not self._installed:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(self._on_event)
            self._installed = True
        return self
