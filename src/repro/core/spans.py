"""Program spans on the profiler's clock.

Every step of a monitor round opens a :func:`span`: a
``jax.profiler.TraceAnnotation`` that lands on the host timeline of the
same profiler trace as the device's operations, so an idle gap on the
device can be put down to the program step the host was in.  With no
profiler attached JAX records nothing; there is no switch.  Span names
and meta are listed in ``docs/OPERATIONS.md`` ("Tracing a round").

:func:`stage` is the same span that also adds its wall time to one key of
a round's ``stage_seconds`` record (``FleetDiagnosis.stage_seconds``).
"""
from __future__ import annotations

import time
from typing import Dict

from jax.profiler import TraceAnnotation

__all__ = ["span", "stage"]


def span(name: str, **meta) -> TraceAnnotation:
    """Context manager for one program span; ``meta`` are numbers the
    host already holds, recorded as the span's metadata.  Meta known only
    at the end is added with ``set_metadata`` on the entered span."""
    return TraceAnnotation(name, **meta)


class stage:
    """:func:`span` ``name`` that also adds the ``perf_counter`` seconds
    spent inside it to ``stages[key]``; ``with`` yields the entered span.
    A plain class rather than a generator context manager: it sits on
    every round, profiled or not."""

    __slots__ = ("_stages", "_key", "_span", "_t0")

    def __init__(self, stages: Dict[str, float], key: str, name: str,
                 **meta) -> None:
        self._stages, self._key = stages, key
        self._span = span(name, **meta)

    def __enter__(self) -> TraceAnnotation:
        self._t0 = time.perf_counter()
        return self._span.__enter__()

    def __exit__(self, *exc) -> None:
        try:
            self._span.__exit__(*exc)
        finally:
            self._stages[self._key] = (self._stages.get(self._key, 0.0)
                                       + time.perf_counter() - self._t0)
