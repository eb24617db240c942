"""Device time of the intersection kernels over the traced window, in %,
averaged over the devices. The kernels are found by the names of the Pallas
bodies they lower to (the HLO custom calls are named after them)."""

KERNELS = r"(multiway_membership|fused_extend|fused_verify|lex_bounds)_kernel"


def read(run):
    if run["trace"] is None:
        return None
    share = run["trace"].op_share(KERNELS)
    return None if share is None else 100.0 * share
