"""Device time of the decode step per decode tick (the median over the
traced ticks that admit nothing)."""


def read(run):
    split = run.step_time()
    return split[0] * 1e-6 if split else None
