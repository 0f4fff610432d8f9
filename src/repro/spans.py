"""Spans and counters the program records about its own phases.

``span(name)`` times a host phase twice over: as a
``jax.profiler.TraceAnnotation``, so a profile puts the phase on the clock
of the device's ops and each idle gap can be put down to it, and as
``(name, t0, t1)`` on ``time.perf_counter_ns`` in a bounded in-process
table, so a caller that timed its own calls on ``time.perf_counter`` can
ask what ran inside them. ``count(name, value)`` logs an int: a host int,
or a device scalar that is fetched only when a snapshot reads it, so the
caller never waits for the device to log one.

Both sinks are always on: a span costs two clock reads, an annotation and
an append. The tables keep the newest ``MAXLEN`` entries each.

Names are dotted (``schedule.rows``, ``match.to_device``): a caller that
profiles the program wraps its own calls in undotted host spans and reads
those by name, and a program span must never be taken for one of them.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Iterator, NamedTuple, Optional, Union

import jax

MAXLEN = 4096

_spans: collections.deque = collections.deque(maxlen=MAXLEN)
_counts: collections.deque = collections.deque(maxlen=MAXLEN)


class Snapshot(NamedTuple):
    spans: list    # (name, t0_ns, t1_ns), oldest first
    counts: list   # (name, t_ns, value)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Record the enclosed block as the span ``name``."""
    t0 = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        _spans.append((name, t0, time.perf_counter_ns()))


def count(name: str, value: Union[int, jax.Array]) -> None:
    """Log ``value`` (a host int or an integer device scalar) under
    ``name``, stamped now."""
    _counts.append((name, time.perf_counter_ns(), value))


def snapshot(since: Optional[int] = None,
             until: Optional[int] = None) -> Snapshot:
    """The spans that lie wholly inside ``[since, until]`` and the counts
    stamped inside it (``perf_counter_ns``; None leaves a side open), each
    count a host int: device scalars are fetched here."""
    lo = -1 if since is None else since
    hi = float("inf") if until is None else until
    spans = [s for s in _spans if lo <= s[1] and s[2] <= hi]
    counts = [c for c in _counts if lo <= c[1] <= hi]
    values = jax.device_get([c[2] for c in counts])  # host-sync: ok (read)
    return Snapshot(spans, [(n, t, int(v))
                            for (n, t, _), v in zip(counts, values)])
