"""Device-idle time inside the benchmark's ``bench.tick`` spans, per tick:
what the host's side of a tick (scheduling, dispatch, the token pull) keeps
the chip waiting."""


def read(run):
    pairs = run.traced_ticks()
    if not pairs:
        return None
    busy = run.busy()
    idle = sum(s.dur - busy.within(s.start, s.end) for _, s in pairs)
    return idle * 1e-6 / len(pairs)
