"""Seconds per campaign in the program's ``hash`` span: the per-tile exact
checksum: key packing, the sort of each triple and the mix.
Read from the ``span.hash`` totals ``repro.obs`` keeps while the
profiler records the window (``bench/spans.py``)."""
from bench.spans import per_campaign


def read(run):
    return per_campaign(run, "hash")
