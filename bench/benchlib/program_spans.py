"""Per-round readings of the program's own host spans.

The program records its spans in memory (``repro.spans``): each record is
``(name, start_ns, end_ns, parent_index)``.  A reading here is the median,
over the recorded ``a3c.round`` spans, of a sum over each round's
descendants; the few set-up and traced rounds among the hundreds of a run
leave the median where it is.  A program without ``repro.spans``, or a
run that recorded no round, reads None.
"""
from __future__ import annotations

import statistics

ROUND = "a3c.round"


def records():
    """The program's span records, or None where it keeps none."""
    try:
        from repro import spans
    except ImportError:
        return None
    return spans.records()


def per_round(recs, name: str, value):
    """Median over the closed ``a3c.round`` spans of ``recs`` of the sum
    of ``value(record)`` over the round's descendants called ``name``."""
    if not recs:
        return None
    # parents precede their children: a record's round is its parent's
    owner = []
    for i, (n, _, _, parent) in enumerate(recs):
        owner.append(i if n == ROUND else
                     owner[parent] if parent >= 0 else -1)
    totals = {i: 0.0 for i, r in enumerate(recs)
              if r[0] == ROUND and r[2] is not None}
    if not totals:
        return None
    for rec, r in zip(recs, owner):
        if rec[0] == name and r in totals:
            totals[r] += value(rec)
    return float(statistics.median(totals.values()))


def ms(rec) -> float:
    return (rec[2] - rec[1]) * 1e-6
