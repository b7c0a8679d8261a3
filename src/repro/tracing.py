"""Host spans of the table server, on the profiler's clock.

``span(name, **args)`` marks a stretch of host work as a
``jax.profiler.TraceAnnotation``, so it lands in the same trace as the
device's operations; keyword arguments (``slab=n``) become the event's
stats.  Every span of the program is named ``kv.<layer>``, so a reduction
tells them from its own.  The device side is marked with
``jax.named_scope("kv.<stage>")`` in the stream seam, which costs nothing
at run time.

Recording is off by default.  An operator who starts ``jax.profiler``
(``start_trace``) calls :func:`enable` so the spans are recorded, and
:func:`disable` before stopping it.  Off, a span is one flag test and a
shared no-op context; the one per-request site (``TableServer.submit``)
tests :data:`enabled` itself.  The switch is process-wide, like the
profiler it feeds.
"""
from __future__ import annotations

import contextlib

import jax

enabled = False
_OFF = contextlib.nullcontext()


def enable() -> None:
    global enabled
    enabled = True


def disable() -> None:
    global enabled
    enabled = False


def span(name: str, **args):
    """A ``TraceAnnotation`` of ``name`` while recording, else a no-op."""
    return jax.profiler.TraceAnnotation(name, **args) if enabled else _OFF
