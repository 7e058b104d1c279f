"""Host spans and device scopes, on the JAX profiler's clock.

The program's only tracing code.  A host span is a
``jax.profiler.TraceAnnotation`` named ``repro:<name>`` whose metadata
carries identifiers and counts (step, request id, token index, rows).
With no profiler session active, entering and leaving one costs about a
microsecond, so spans stay on.

A device scope is a ``jax.named_scope`` from :data:`SCOPES`: trace-time
metadata that lands in the ``op_name`` of every HLO op it encloses and
costs nothing at run time.  A forward op reads ``.../ssd/...``, its
backward ``.../transpose(jvp(...))/.../ssd/...``, and remat's recompute
``.../rematted_computation/.../ssd/...``.
"""
from __future__ import annotations

import functools
import gc

import jax

PREFIX = "repro:"

#: every device scope the model and the train step open
SCOPES = ("embed", "attention", "mlp", "moe", "ssm", "ssd", "rglru",
          "head_loss", "weight_cast", "lambda_decode", "codec",
          "optimizer")


def scope(name: str):
    """``jax.named_scope(name)`` for a name in :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; known: {SCOPES}")
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: trace the whole function under :func:`scope`."""
    scope(name)

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A host span ``repro:<name>``; ``meta`` rides along as metadata."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


def step_span(step: int, **meta) -> jax.profiler.StepTraceAnnotation:
    """The span of one train step, which the profiler counts as a step."""
    return jax.profiler.StepTraceAnnotation(PREFIX + "train.step",
                                            step_num=step, **meta)


_gc_open = []


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        s = span("host.gc", generation=info["generation"])
        s.__enter__()
        _gc_open.append(s)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def install_gc_spans() -> None:
    """Put one ``repro:host.gc`` span on the trace per Python collection
    (``generation`` as metadata).  Installing twice is a no-op."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
