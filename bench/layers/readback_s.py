"""Seconds per campaign in the program's ``readback`` span: the copy of the
result blocks from the device into a host array.
Read from the ``span.readback`` totals ``repro.obs`` keeps while the
profiler records the window (``bench/spans.py``)."""
from bench.spans import per_campaign


def read(run):
    return per_campaign(run, "readback")
