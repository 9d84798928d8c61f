"""Seconds per campaign inside ``SimilarityResult.checksum()``: the host
assembly of global indices from ``tiles()`` and the exact checksum.  The
benchmark's own host span, also written as a profiler annotation."""


def read(run):
    times = [c["assemble_s"] for c in run.campaigns]
    return sum(times) / len(times) if times else None
