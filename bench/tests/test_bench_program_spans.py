"""Metrics that read the program's in-memory spans
(``benchlib/program_spans.py`` and the metric files that use it), on
record lists built by hand and on spans the program records."""
import importlib
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import program_spans                       # noqa: E402

MS = 1_000_000


def metric(name):
    return importlib.import_module(f"metrics.{name}")


def a3c_round(recs, t0, serve_ms, update_ms, reads):
    """Append one round's records at ``t0`` ms in the program's nesting:
    serves, a flush, then the trainer's reads and updates."""
    r = len(recs)
    recs.append(["a3c.round", t0 * MS, None, -1])
    t = t0
    for ms in serve_ms:
        recs.append(("a3c.serve", t * MS, (t + ms) * MS, r))
        t += ms
    recs.append(("mcc.flush", t * MS, (t + 1) * MS, r))
    t += 1
    tr = len(recs)
    recs.append(["a3c.train", t * MS, None, r])
    for ms in update_ms:
        recs.append(("host_read", t * MS, (t + 1) * MS, tr))
        recs.append(("a3c.update", (t + 1) * MS, (t + 1 + ms) * MS, tr))
        t += 1 + ms
    for _ in range(reads - len(update_ms)):
        recs.append(("host_read", t * MS, (t + 1) * MS, tr))
        t += 1
    recs[tr][2] = recs[r][2] = t * MS
    recs[tr] = tuple(recs[tr])
    recs[r] = tuple(recs[r])


def records():
    recs = [("setup", 0, 5 * MS, -1)]
    a3c_round(recs, 10, [4.0, 6.0], [100.0], reads=2)
    a3c_round(recs, 200, [3.0, 5.0], [120.0], reads=2)
    a3c_round(recs, 400, [30.0, 2.0], [90.0, 50.0], reads=3)
    # the drain after the window: a train outside any round
    recs.append(("a3c.train", 700 * MS, 800 * MS, -1))
    recs.append(("host_read", 700 * MS, 701 * MS, len(recs) - 1))
    recs.append(("a3c.update", 701 * MS, 799 * MS, len(recs) - 2))
    return recs


@pytest.mark.parametrize("name,want", [
    ("host_reads_per_round", 2.0),            # 2, 2, 3
    ("trainer_host_ms_per_round", 120.0),     # 100, 120, 140
    ("serve_host_ms_per_round", 10.0),        # 10, 8, 32
])
def test_reads_a_record_list(name, want):
    assert metric(name).from_records(records()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["host_reads_per_round",
                                  "trainer_host_ms_per_round",
                                  "serve_host_ms_per_round"])
def test_no_round_reads_none(name, monkeypatch):
    m = metric(name)
    assert m.from_records([]) is None
    assert m.from_records([("a3c.train", 0, MS, -1),
                           ("host_read", 0, MS, 0)]) is None
    assert m.from_records([("a3c.round", 0, None, -1)]) is None  # open
    # a program without the span recorder reads None and does not raise
    import repro
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    monkeypatch.delattr(repro, "spans", raising=False)
    assert m.read(None) is None


def test_reads_the_programs_spans():
    from repro import spans
    spans.clear()
    try:
        for _ in range(3):
            with spans.span("a3c.round", round=0):
                with spans.span("a3c.serve", gmi=0):
                    pass
                with spans.span("a3c.train"):
                    with spans.span("host_read"):
                        pass
                    with spans.span("a3c.update"):
                        pass
                    with spans.span("host_read"):
                        pass
        assert metric("host_reads_per_round").read(None) == 2.0
        for name in ("trainer_host_ms_per_round", "serve_host_ms_per_round"):
            assert 0.0 < metric(name).read(None) < 1e3
    finally:
        spans.clear()
