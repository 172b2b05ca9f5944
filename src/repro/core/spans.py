"""Host spans that name the program's phases in a JAX profiler trace.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``repro:<name>``.  Under ``jax.profiler.trace`` it lands on the
profiler's host plane, on the clock the device events are aligned to, with
``args`` as its stats; with no profiler active it costs one small C++
object.  Host code only: never call it inside a jitted function (the
device side is named with ``jax.named_scope``).

``Phases`` runs one call as back-to-back spans that never overlap, all
carrying the same ``call`` number, so a reader of the trace can group the
phases of one call and divide the counts it records by their times.
"""
from __future__ import annotations

import itertools

import jax

PREFIX = "repro:"

_CALLS = itertools.count()


def span(name: str, **args):
    """A host span ``repro:<name>``; ``args`` become its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


class Phases:
    """One call as a sequence of spans ``<prefix>.<phase>``.

    ``enter(phase)`` ends the open span and starts the next, so the phases
    tile the call without gaps or overlaps; leaving the ``with`` block ends
    the last one.  Every span carries ``args`` plus a per-process ``call``
    number; ``annotate`` adds counts once they are known, to the open span
    and to every later one.
    """

    def __init__(self, prefix: str, first: str, **args):
        self.prefix = prefix
        self.args = dict(args, call=next(_CALLS))
        self._open = None
        self.enter(first)

    def enter(self, phase: str) -> None:
        self.close()
        self._open = span(f"{self.prefix}.{phase}", **self.args)
        self._open.__enter__()

    def annotate(self, **args) -> None:
        self.args.update(args)
        if self._open is not None:
            self._open.set_metadata(**args)

    def close(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def __enter__(self) -> "Phases":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
