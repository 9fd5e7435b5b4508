"""Device time of prefill per 1000 prompt tokens, in the traced part of
the window: the time of the prefill executables (``jit_serve_prefill``,
``jit_serve_prefill_chunk``, ...) and of those that write a prefill into
the cache (``jit_serve_write``, ``jit_serve_paged_write``), over the prompt
tokens admitted in the traced ticks. Reads nothing from a program that does
not name its executables."""

from bench import executables


def read(run):
    ticks = executables.by_tick(run, ("prefill*", "*write*"))
    if not ticks:
        return None
    ns = sum(n for _, n in ticks)
    tokens = sum(sum(t.admitted) for t, _ in ticks)
    if not ns or not tokens:
        return None
    return ns * 1e-6 / (tokens / 1000.0)
