"""Host spans in the profiler's own trace, written from benchmark/
files around calls into the program. Off (a no-op context) unless the
run traces: the untraced window pays nothing for them."""

from __future__ import annotations

import contextlib

_on = False
_NOOP = contextlib.nullcontext()


def enable(on: bool) -> None:
    global _on
    _on = on


def span(name: str):
    if not _on:
        return _NOOP
    import jax
    return jax.profiler.TraceAnnotation(name)
