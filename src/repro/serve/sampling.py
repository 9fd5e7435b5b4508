"""Token sampling for the serve paths (static ``serve_batch`` and engine).

One abstraction serves both: a :class:`Sampler` carries the per-request
policy, and :func:`sample_batch` applies a *mixed* batch of policies in one
jit-able call (greedy and sampled requests share a decode step).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.layers.numerics import f32_upcast
from repro.tracing import SAMPLE, layer_scope

__all__ = ["Sampler", "GREEDY", "sample_batch"]


@dataclasses.dataclass(frozen=True)
class Sampler:
    """Next-token policy: ``temperature <= 0`` is greedy argmax, otherwise
    categorical sampling over ``logits / temperature``."""

    temperature: float = 0.0

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0

    def __call__(self, logits, rng=None):
        """Sample next tokens from ``logits (B, vocab)`` → ``(B,) int32``.

        ``rng`` is required (a ``jax.random`` key) unless greedy.
        """
        if self.greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if rng is None:
            raise ValueError("non-greedy Sampler needs an rng key")
        scaled = f32_upcast(logits) / self.temperature
        return jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)


#: the default policy (argmax decode)
GREEDY = Sampler(0.0)


@layer_scope(SAMPLE)
def sample_batch(logits, temperature, greedy_mask, rng):
    """Per-row mixed sampling: ``logits (B, vocab)`` → ``(B,) int32``.

    ``temperature (B,)`` and ``greedy_mask (B,)`` carry each slot's policy;
    greedy rows take the argmax, the rest sample categorically at their own
    temperature. Shapes are fixed in the slot count, so the engine jits
    this once.
    """
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(
        rng, f32_upcast(logits) / temp, axis=-1).astype(jnp.int32)
    return jnp.where(greedy_mask, greedy_tok, sampled)
