"""Seconds per campaign inside ``SimilarityEngine.run``: request validation,
host encode, host-to-device staging, the device program and the readback.
The benchmark's own host span, also written as a profiler annotation."""


def read(run):
    times = [c["engine_s"] for c in run.campaigns]
    return sum(times) / len(times) if times else None
