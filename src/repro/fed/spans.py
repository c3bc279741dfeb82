"""Named phases of a run: host spans and device scopes.

A :class:`Spans` recorder marks each host phase of
:func:`repro.fed.engine.run` twice.  ``with spans(name):`` opens a
``jax.profiler.TraceAnnotation`` of that name -- a host event in any
profiler trace taken around the run, on the same clock as the device's
operations, and inert when no profiler runs -- and adds the phase's
*self seconds* to ``spans.seconds[name]`` (the run's ``History.spans``):
its ``perf_counter`` duration minus that of the spans opened inside it.
A span adds no host sync and no device work, and changes the order of
no dispatch.

:func:`scoped` names a traced function's operations instead
(``jax.named_scope``): the name lands in the op metadata of the compiled
program, and so on the device's operations in a trace.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List

import jax


class Spans:
    """Self seconds of named host phases, by name (``seconds``)."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self._open: List[_Span] = []          # innermost last

    def __call__(self, name: str) -> "_Span":
        return _Span(name, self)


class _Span:
    """One phase; ``seconds`` is its whole duration once it has ended."""

    def __init__(self, name: str, owner: Spans):
        self.name, self.owner = name, owner
        self.seconds = self._nested = self._t0 = 0.0
        self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.owner._open.append(self)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        opened = self.owner._open
        opened.pop()
        if opened:
            opened[-1]._nested += self.seconds
        acc = self.owner.seconds
        acc[self.name] = acc.get(self.name, 0.0) + self.seconds - self._nested
        return False


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
