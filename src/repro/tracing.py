"""The names the profiler sees in the serve path.

Three kinds of name, all recorded into the JAX profiler's own trace and
nothing else; with no trace running none of them records or costs anything:

* **layer scopes** — ``jax.named_scope`` names on the device operations of
  each layer kind, set where the layer's math is (:func:`layer_scope`). They
  land in the HLO op metadata (``op_name``, the trace's ``tf_op``) as a path
  component, e.g. ``jit(serve_decode)/while/body/ssd/dot_general``. Work
  outside every layer (the layer loops' indexed reads and writes of
  weights, state and the KV pool, norms, residual adds) carries none.
* **executables** — every callable the serve engine jits is named
  ``serve_<name>`` (:func:`executable`), so its XLA module reads
  ``jit_serve_<name>``: ``jit_serve_decode`` (every live-block bucket),
  ``jit_serve_prefill``, ``jit_serve_write``, ``jit_serve_sample``, ...
* **host spans** — ``jax.profiler.TraceAnnotation`` names at the engine
  tick's phase boundaries (``serve.*`` below), on the same clock as the
  device events.

``docs/serving.md`` ("Tracing") says how to record a window.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax

__all__ = ["EMBED", "SSD", "ATTENTION", "MLP", "MOE", "LOGITS", "SAMPLE",
           "LAYER_SCOPES", "EXECUTABLE_PREFIX", "TICK", "SCHEDULE", "ADMIT",
           "PULL", "PREFILL_CHUNK", "DECODE", "COMMIT", "VERIFY",
           "layer_scope", "executable"]

# -- layer scopes -------------------------------------------------------------
EMBED = "embed"            # token lookup
SSD = "ssd"                # the Mamba-2 mixer, decode and chunked
ATTENTION = "attention"    # projections, rope, KV write, score reduction
MLP = "mlp"                # feed-forward blocks
MOE = "moe"                # routed experts
LOGITS = "logits"          # the output projection
SAMPLE = "sample"          # next-token sampling

#: every layer scope; an op belongs to the outermost of these that its
#: metadata name holds as a path component, and to no layer if it holds none
LAYER_SCOPES = (EMBED, SSD, ATTENTION, MLP, MOE, LOGITS, SAMPLE)

# -- executables --------------------------------------------------------------
#: function-name prefix of every engine callable (module ``jit_serve_<name>``)
EXECUTABLE_PREFIX = "serve_"

# -- host spans (one tick) ---------------------------------------------------
TICK = "serve.tick"                    # the tick's body
SCHEDULE = "serve.schedule"            # preemption and admission decisions
ADMIT = "serve.admit"                  # one admission (uid, prompt_len)
PULL = "serve.pull"                    # a device-to-host token sync
PREFILL_CHUNK = "serve.prefill_chunk"  # one chunk of a chunked prefill
DECODE = "serve.decode"                # decode inputs, bucket, dispatches
COMMIT = "serve.commit"                # bookkeeping after the token pull
VERIFY = "serve.verify"                # the speculative tick's dispatches


def layer_scope(name: str) -> Callable[[Callable], Callable]:
    """Decorator: trace the function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def executable(name: str, fn: Callable) -> Callable:
    """``fn`` under the function name ``serve_<name>``, which ``jax.jit``
    gives its XLA module (``jit_serve_<name>``)."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        return fn(*args, **kwargs)
    call.__name__ = call.__qualname__ = EXECUTABLE_PREFIX + name
    return call
