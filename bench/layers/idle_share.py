"""Share of the traced window in which no operation ran on the device, in
percent, averaged over the cell's chips."""


def read(run):
    t = run.trace
    if not t:
        return None
    busy = sum(t["busy_s"]) / len(t["busy_s"])
    return 100.0 * (1.0 - busy / t["window_s"])
