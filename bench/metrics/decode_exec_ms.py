"""Device time of the decode executable per decoding tick: the mean, over
the traced ticks that decode, of the time of the ``jit_serve_decode``
modules that began in the tick (every live-block bucket of the paged
decode shares the name). A mean, so that the layers of the step add up to
it. Reads nothing from a program that does not name its executables."""

from bench import executables


def read(run):
    ticks = executables.by_tick(run, ("decode",))
    if not ticks:
        return None
    decoding = [ns for t, ns in ticks if t.decoded]
    if not decoding or not sum(decoding):
        return None
    return sum(decoding) * 1e-6 / len(decoding)
