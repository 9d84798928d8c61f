"""Seconds per campaign in the program's ``dispatch`` span: the call of the
jitted campaign program until it returns: trace, lowering, compile or cache
load, enqueue.
Read from the ``span.dispatch`` totals ``repro.obs`` keeps while the
profiler records the window (``bench/spans.py``)."""
from bench.spans import per_campaign


def read(run):
    return per_campaign(run, "dispatch")
