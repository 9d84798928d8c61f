"""Milliseconds per campaign that collective operations (the ``ppermute``
ring) ran on the busiest chip, from the device trace."""


def read(run):
    t = run.trace
    if not t or not t["campaigns"] or not any(t["collective_s"]):
        return None
    return 1e3 * max(t["collective_s"]) / t["campaigns"]
