"""The least time the chips could take for one campaign over the device's
busy time per campaign on the busiest chip, in percent.  The least time is
the larger of the campaign's operations at the int8 peak and its bytes at
the HBM bandwidth, both from ``bench/work.py`` and ``bench/peaks.json``, so
the share reads the same work whatever implements it."""


def read(run):
    t = run.trace
    if not t or not t["campaigns"] or run.peak is None:
        return None
    least, _ = run.work.least_time_s(run.cfg, run.stages, run.peak, run.chips)
    busy = max(t["busy_s"]) / t["campaigns"]
    return 100.0 * least / busy if busy > 0 else None
