"""The popcount bit-GEMM's share of its roofline, in percent: the
campaign's least time (``bench/work.py``'s ``least_time_s``: its operations
at the int8 peak or its bytes at the HBM bandwidth, the yardstick of
``device_roofline``) over the device seconds per campaign of the
``metric2_pop*`` kernels.  A plane-dot kernel reads on the same scale.

Nothing is read where the trace has no device plane or no popcount op, or
where the registry's ``path.fused-popcount`` counter did not count every
campaign of the process, warm-up included: a campaign that took another
contraction path cannot pass as a number."""

PREFIX = "metric2_pop"
COUNTER = "path.fused-popcount"


def _every_campaign_popcount(run) -> bool:
    try:
        from repro.obs.metrics import default_registry
    except ImportError:
        return False
    paths = {k: v for k, v in default_registry().snapshot().items()
             if k.startswith("path.")}
    pop = paths.get(COUNTER, 0)
    return pop >= len(run.campaigns) + 1 and pop == sum(paths.values())


def read(run):
    t = run.trace
    if not t or not t["campaigns"] or run.peak is None:
        return None
    kernel_s = sum(s for name, s in t["device_ops"] if name.startswith(PREFIX))
    if kernel_s <= 0 or not _every_campaign_popcount(run):
        return None
    least, _ = run.work.least_time_s(run.cfg, run.stages, run.peak, run.chips)
    return 100.0 * least / (kernel_s / t["campaigns"])
