"""Seconds per campaign in the program's ``encode`` span: the host's
preparation of the payload: resolving the request against the cohort,
padding and the numpy bit-plane encode.
Read from the ``span.encode`` totals ``repro.obs`` keeps while the
profiler records the window (``bench/spans.py``)."""
from bench.spans import per_campaign


def read(run):
    return per_campaign(run, "encode")
