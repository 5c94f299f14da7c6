"""``trainer_host_ms_per_round``: host time of the A3C trainer updates in
one round, the summed ``a3c.update`` spans under an ``a3c.round`` span of
the program (``rl/a3c.py``), median over the run's rounds, in ms."""
from benchlib import program_spans


def from_records(recs):
    return program_spans.per_round(recs, "a3c.update", program_spans.ms)


def read(ctx):
    return from_records(program_spans.records())
