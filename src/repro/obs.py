"""Spans and latency histograms of the serving path.

:func:`span` opens a :class:`jax.profiler.TraceAnnotation`, so the span
lands in the same profiler capture as the device's planes, on the same
clock, with its ``ids`` as the event's arguments.  Tracing is on exactly
while a profiler capture runs; otherwise ``span`` hands back one shared
no-op context and the ids are never formatted.

:class:`LatencyHistogram` keeps durations in fixed log-spaced buckets, so
memory stays constant however long the service runs and no tail is dropped.
"""
from __future__ import annotations

import bisect
import itertools
import math
from typing import List, Optional

from jax.profiler import TraceAnnotation


class _Off:
    """What :func:`span` returns while no capture runs."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **ids) -> None:
        return None


_OFF = _Off()


def span(name: str, **ids):
    """A ``with`` context naming one stretch of host work (``ann.launch``,
    ``packed.compile``, ...).  ``ids`` tie the spans of one request or
    launch together; ``set_metadata(**ids)`` on the context adds ids known
    only at its end."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return TraceAnnotation(name, **ids)


class LatencyHistogram:
    """Durations in seconds, counted in buckets ``PER_DECADE`` to a decade
    from 1 us to 1,000 s (under- and overflow in the end buckets).  A
    percentile reads the geometric middle of its bucket, within 1.8% of the
    true value.  Not locked: the owner serialises ``add``, ``clear`` and the
    reads."""

    PER_DECADE = 64
    LOW_S = 1e-6
    DECADES = 9

    def __init__(self) -> None:
        self.counts: List[int] = [0] * (self.PER_DECADE * self.DECADES)
        self.n = 0

    def add(self, seconds: float) -> None:
        k = 0
        if seconds > self.LOW_S:
            k = min(int(math.log10(seconds / self.LOW_S) * self.PER_DECADE),
                    len(self.counts) - 1)
        self.counts[k] += 1
        self.n += 1

    def clear(self) -> None:
        self.counts = [0] * len(self.counts)
        self.n = 0

    def percentile(self, q: float) -> Optional[float]:
        """The ``q``-th percentile in seconds; None when nothing was added."""
        if not self.n:
            return None
        rank = max(1, math.ceil(q / 100.0 * self.n))
        k = bisect.bisect_left(list(itertools.accumulate(self.counts)), rank)
        return self.LOW_S * 10 ** ((k + 0.5) / self.PER_DECADE)
