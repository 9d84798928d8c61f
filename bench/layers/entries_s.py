"""Seconds per campaign in the program's ``entries`` span: the per-block
gather of global indices and values, in every scan of the result's tiles.
Read from the ``span.entries`` totals ``repro.obs`` keeps while the
profiler records the window (``bench/spans.py``)."""
from bench.spans import per_campaign


def read(run):
    return per_campaign(run, "entries")
