"""Share of the traced window in which the device ran no operation."""


def read(run):
    pairs = run.traced_ticks()
    if not pairs:
        return None
    lo = pairs[0][1].start
    hi = pairs[-1][1].end
    return 100.0 * (1.0 - run.busy().within(lo, hi) / (hi - lo))
