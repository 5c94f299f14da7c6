"""Host spans of the program, on the profiler's clock and in memory.

``span(name, **args)`` marks a region of host code.  It enters
``jax.profiler.TraceAnnotation(name, **args)``, so under an active
profiler the region shows on the host plane of the trace, on the same
clock as the device's ops; and it appends a record to a process-wide ring
of the last ``CAPACITY`` spans, so the program and its readers can time
the region without a profiler::

    with span("a3c.round", round=3) as s:
        ...
    s.seconds            # the region's wall time

A record is ``(name, start_ns, end_ns, parent_index)``: ``time.
perf_counter_ns`` at entry and exit (``end_ns`` is None while the span is
open) and the index, in :func:`records`, of the span that enclosed it on
the same thread (-1 for none, or when the ring has dropped it).  The
ring keeps the newest records and drops the oldest.

Off the profiler a span costs two clock reads, one inert annotation and
one append.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import List, Optional, Tuple

import jax

CAPACITY = 8192

# [seq, name, start_ns, end_ns, parent_seq]; seq numbers spans in order
# of entry over the process, so parents survive the ring's drops by name
_ring: collections.deque = collections.deque(maxlen=CAPACITY)
_seq = itertools.count()
_local = threading.local()

Record = Tuple[str, int, Optional[int], int]


class span:
    """Context manager: one host span (see the module docstring)."""

    __slots__ = ("_annotation", "_rec", "seconds")

    def __init__(self, name: str, **args):
        self._annotation = jax.profiler.TraceAnnotation(name, **args)
        self._rec = [0, name, 0, None, -1]
        self.seconds: Optional[float] = None

    def __enter__(self) -> "span":
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        rec = self._rec
        rec[0] = next(_seq)
        rec[4] = stack[-1] if stack else -1
        stack.append(rec[0])
        self._annotation.__enter__()
        rec[2] = time.perf_counter_ns()
        _ring.append(rec)
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        rec = self._rec
        rec[3] = end
        _local.stack.pop()
        self.seconds = (end - rec[2]) * 1e-9


def records() -> List[Record]:
    """The ring's records, oldest first, with parents as indices into the
    returned list."""
    recs = sorted(_ring, key=lambda r: r[0])
    index = {r[0]: i for i, r in enumerate(recs)}
    return [(name, start, end, index.get(parent, -1))
            for _, name, start, end, parent in recs]


def clear() -> None:
    """Empty the ring (open spans still close normally)."""
    _ring.clear()
