"""Faults planted under the served path for the check's own tests: each
breaks what a decode tick produces, in the engine the window drives."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _wrap_decode(engine, wrap):
    """Apply ``wrap(step)`` to the decode step(s) the engine dispatches."""
    if engine.paged:
        build = engine._decode_for
        engine._decode_for = lambda hw: wrap(build(hw))
    else:
        engine._decode = wrap(engine._decode)


def state_unchanged(engine):
    """The decode step returns the cache it was given (its state does not
    advance); the cache is donated, so a copy is what comes back."""
    def wrap(step):
        def f(p, c, t):
            keep = jax.tree.map(jnp.copy, c)
            logits, _ = step(p, c, t)
            return logits, keep
        return f
    _wrap_decode(engine, wrap)


def half_batch(engine):
    """Half of the batch is left out of the decode step: the requests with
    odd ids get zero logits (chosen by request, not by slot, since a light
    load leaves most requests in the lowest slots)."""
    def wrap(step):
        def f(p, c, t):
            logits, c = step(p, c, t)
            out = [s for s, inf in engine._inflight.items()
                   if inf.request.uid % 2]
            return logits.at[jnp.asarray(out, jnp.int32)].set(0.0), c
        return f
    _wrap_decode(engine, wrap)


def token_altered(engine):
    """Every sampled token is moved to the next id where it is produced."""
    sample = engine._sample

    def f(logits, *args):
        return (sample(logits, *args) + 1) % logits.shape[-1]
    engine._sample = f


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
