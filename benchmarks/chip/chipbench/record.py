"""What one run records on the host: spans, values and compile events.

The traffic runners time their calls into the program here; the metric
readers reduce the record.  With tracing on, every span is also written
into the profiler's trace as a ``bench:<name>`` annotation, on the same
clock as the device's operations.
"""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, List, Tuple

#: the JAX event that fires for every backend compile, a load from the
#: persistent cache included
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Record:
    def __init__(self, traced: bool):
        self.traced = traced
        self.spans: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        self.values: Dict[str, object] = {}
        self.compiles = 0
        self._listening = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation("bench:" + name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans[name].append((t0, time.perf_counter()))

    def listen_for_compiles(self) -> None:
        """Count backend compiles (and loads from the persistent cache)
        from now on."""
        import jax.monitoring

        if not self._listening:
            jax.monitoring.register_event_duration_secs_listener(
                self._on_event)
            self._listening = True

    def _on_event(self, name: str, *_args, **_kw) -> None:
        if name == COMPILE_EVENT:
            self.compiles += 1
