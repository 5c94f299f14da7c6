"""``host_reads_per_round``: blocking device-to-host reads in one async
A3C round, the ``host_read`` spans under an ``a3c.round`` span of the
program (``rl/a3c.py``), median over the run's rounds."""
from benchlib import program_spans


def from_records(recs):
    return program_spans.per_round(recs, "host_read", lambda rec: 1.0)


def read(ctx):
    return from_records(program_spans.records())
