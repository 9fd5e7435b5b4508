"""The program's named executables in a trace: the device time of the XLA
modules ``jit_serve_<name>`` (``repro.tracing.executable``) that began in
each traced tick. A program that does not name its executables so gives
nothing to read."""

from __future__ import annotations

import fnmatch
from typing import List, Optional, Sequence, Tuple


def module_prefix() -> Optional[str]:
    """``jit_serve_``: what ``jax.jit`` puts before a callable's name, and
    the program's prefix; None for a program without the prefix."""
    try:
        from repro.tracing import EXECUTABLE_PREFIX
    except ImportError:         # a program from before executables had names
        return None
    return "jit_" + EXECUTABLE_PREFIX


def by_tick(run, patterns: Sequence[str]
            ) -> Optional[List[Tuple[object, int]]]:
    """For every traced tick, ``(tick, ns)``: the device time of the
    executables whose name after ``jit_serve_`` matches one of the glob
    ``patterns`` (``"decode"``, ``"prefill*"``) and that began in the tick.
    None where the run was not traced or the program names no executable.
    The module events read ``jit_serve_decode(<fingerprint>)``."""
    prefix = module_prefix()
    ticks = run.device_events("modules")
    if prefix is None or not ticks:
        return None
    out = []
    for tick, evs in ticks:
        ns = 0
        for e in evs:
            name = e.name.split("(", 1)[0]
            if name.startswith(prefix) and any(
                    fnmatch.fnmatchcase(name[len(prefix):], p)
                    for p in patterns):
                ns += e.dur
        out.append((tick, ns))
    return out
