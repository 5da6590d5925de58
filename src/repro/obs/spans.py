"""Timing spans on the profiler's clock.

``span(name, **args)`` is a profiler ``TraceAnnotation`` while a
``jax.profiler`` session is collecting, and one shared no-op context
otherwise: there is no switch of its own, the spans follow the session. A
span's keyword args land as stats on its host event (``ProfileData``
reads them back), and ``set_metadata`` adds more before it closes. Host
spans and device operations then share one clock, so a gap in the
device's work can be named by what the host was doing.

This module also hooks Python's garbage collector once per process: each
collection that runs while a session collects is a ``repro.gc`` span with
its ``generation``.

It imports nothing at load time: a process that never imported JAX has no
profiler session, and its spans stay no-ops.
"""
from __future__ import annotations

import gc
import sys
from typing import Any


class _NullSpan:
    """The span used while no profiler session collects."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **args: Any) -> None:
        return None


NULL_SPAN = _NullSpan()
_annotation = None


def _trace_annotation():
    """JAX's TraceAnnotation once ``jax.profiler`` is imported, else None."""
    global _annotation
    if _annotation is None:
        mod = sys.modules.get("jax.profiler")
        _annotation = getattr(mod, "TraceAnnotation", None)
    return _annotation


def span(name: str, **args: Any):
    """A host span named ``name`` with ``args`` as its stats."""
    ta = _trace_annotation()
    if ta is not None and ta.is_enabled():
        return ta(name, **args)
    return NULL_SPAN


# the repro.gc span of the collection under way, if it began while a
# session collected (a session may start or stop in between)
_gc_open: list = []


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        sp = span("repro.gc", generation=info.get("generation", -1))
        if sp is not NULL_SPAN:
            sp.__enter__()
            _gc_open.append(sp)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


gc.callbacks.append(_gc_span)
