"""Mean share of the slots that hold a request, over the decode ticks of the
window (the harness counts the scheduler's active slots after each tick)."""


def read(run):
    ticks = [t for t in run.ticks if t.decoded and t.end <= run.seconds]
    if not ticks:
        return None
    return 100.0 * sum(len(t.decoded) for t in ticks) \
        / (len(ticks) * run.n_slots)
