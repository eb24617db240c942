"""Traced window over the scheduler steps (``ScheduleStats.steps``) in it:
milliseconds per operator batch, host and device together."""


def read(run):
    steps = run["counters"].get("steps")
    if not steps or run["trace"] is None:
        return None
    return run["trace"].window_s * 1e3 / steps
