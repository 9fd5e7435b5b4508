"""Device time of prefill per 1000 prompt tokens prefilled, in the traced
part of the window: the executables' time less the decode step's."""


def read(run):
    split = run.step_time()
    if not split:
        return None
    _, ns, ticks = split
    tokens = sum(sum(t.admitted) for t in ticks)
    if not tokens or ns <= 0:
        return None
    return ns * 1e-6 / (tokens / 1000.0)
