"""Share of the traced window in which the device ran no operation, in %:
1 - (union of the device's operation intervals / window), averaged over the
devices."""


def read(run):
    if run["trace"] is None or not run["trace"].devices:
        return None
    idle = run["trace"].idle_share()
    return None if idle is None else 100.0 * idle
