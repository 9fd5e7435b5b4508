"""Abstract MOA strategy — the pluggable core of the paper's design space.

The paper's object of study is the *multi-operand adder*: a reduction node
with hundreds to thousands of operands, and the question of how to schedule
it (spatial tree, §3.1 serialization, §3.2 approximate adders). This module
makes that scheduling axis a first-class API:

  * :class:`MOAStrategy` — abstract base. A strategy knows how to ``sum``
    operands over an axis, how to ``dot`` two matrices (scheduling the
    contraction dimension), and how to ``cost`` itself analytically.
  * Every strategy is a frozen dataclass, so it is hashable, comparable and
    safe to embed in a :class:`repro.configs.base.ModelConfig` or close over
    inside a jitted train step.
  * ``backend`` selects the executing substrate per call site:
    ``"jnp"`` (pure-jnp reference paths), ``"pallas"`` (the TPU kernels in
    :mod:`repro.kernels`, interpret-mode on CPU) or ``"auto"`` (pallas iff
    the default JAX backend is TPU and no multi-device mesh is active).
  * Each strategy serializes to a canonical *spec string* —
    ``"serial?chunk=512"`` — parsed back by :func:`repro.moa.resolve`; the
    round trip ``resolve(spec).spec == spec`` holds for canonical specs.

Concrete strategies register themselves in :mod:`repro.moa.registry`;
adding a new scheduling strategy (e.g. a two-level tree-of-serial or a
stochastic-rounding accumulator) is one subclass + one
``@register_strategy`` decoration — no cross-cutting edits.
"""

from __future__ import annotations

import abc
import dataclasses
from typing import Any, ClassVar, Dict, Optional

import jax
import jax.numpy as jnp

from repro.parallel import active_context

__all__ = ["MOAStrategy", "BACKENDS", "resolved_backend"]

BACKENDS = ("auto", "jnp", "pallas")


def resolved_backend(backend: str) -> str:
    """Map ``"auto"`` to the substrate the process is actually running on.

    The Pallas kernels are single-device programs: GSPMD cannot partition
    a Mosaic call by the operands' shardings. Under an active multi-device
    sharding context (:func:`repro.parallel.activate`) ``"auto"`` therefore
    resolves to the jnp paths, whose XLA ops partition with their operands,
    and an explicit ``"pallas"`` is refused.
    """
    mesh, _ = active_context()
    if mesh is not None and mesh.size > 1:
        if backend == "pallas":
            raise ValueError(
                "the Pallas MOA kernels run on one device and cannot be "
                f"partitioned over a {mesh.size}-device mesh; use backend "
                "'jnp' or 'auto' under a mesh")
        return "jnp"
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _format_value(v: Any) -> str:
    return str(v)


@dataclasses.dataclass(frozen=True)
class MOAStrategy(abc.ABC):
    """How a large-fan-in reduction is scheduled, and on what substrate.

    Attributes:
      backend: ``"auto"`` | ``"jnp"`` | ``"pallas"``. ``auto`` resolves to
        the Pallas kernels on one TPU device and the jnp reference paths
        elsewhere (on CPU, and under a multi-device mesh).
    """

    backend: str = "auto"

    #: registry key; set by each concrete subclass
    name: ClassVar[str] = ""
    #: True for strategies whose arithmetic is defined on integers only
    integer_only: ClassVar[bool] = False

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; expected one of {BACKENDS}")

    # ---- spec-string round trip -------------------------------------------
    @property
    def spec(self) -> str:
        """Canonical spec string: ``name`` + sorted non-default params.

        ``resolve(s.spec) == s`` for every strategy ``s``; conversely
        ``resolve(spec).spec == spec`` whenever ``spec`` is canonical
        (params alphabetical, defaults omitted).
        """
        params = sorted(
            f"{f.name}={_format_value(getattr(self, f.name))}"
            for f in dataclasses.fields(self)
            if getattr(self, f.name) != f.default
        )
        return self.name + ("?" + "&".join(params) if params else "")

    def __str__(self) -> str:
        return self.spec

    # ---- backend / dtype plumbing -----------------------------------------
    def resolve_backend(self) -> str:
        return resolved_backend(self.backend)

    def accum_dtype_for(self, operand_dtype) -> jnp.dtype:
        """Accumulator dtype: int32 for integer operands, else ``accum``.

        Mirrors the hardware: the MXU hard-wires f32 accumulation for float
        operands and int32 for int8 — a strategy's ``accum`` field only
        chooses among float precisions.
        """
        if jnp.issubdtype(jnp.dtype(operand_dtype), jnp.integer):
            return jnp.dtype(jnp.int32)
        return jnp.dtype(getattr(self, "accum", "float32"))

    def replace(self, **updates) -> "MOAStrategy":
        return dataclasses.replace(self, **updates)

    def _check_operands(self, dtype) -> None:
        if self.integer_only and not jnp.issubdtype(jnp.dtype(dtype),
                                                    jnp.integer):
            raise TypeError(
                f"{self.name!r} strategy requires integer operands, got "
                f"{jnp.dtype(dtype).name}")

    @classmethod
    def bench_specs(cls) -> tuple:
        """Representative spec strings for registry-driven benchmark sweeps.

        Benchmarks enumerate ``available_strategies()`` and call this per
        class, so a newly registered strategy appears in the sweeps without
        editing any benchmark. Default: the bare name.
        """
        return (cls.name,)

    # ---- the strategy interface -------------------------------------------
    @abc.abstractmethod
    def sum(self, x, *, axis: int = -1) -> jax.Array:
        """Reduce ``x`` over ``axis``; returns the accumulator dtype."""

    @abc.abstractmethod
    def dot(self, a, b, *, out_dtype: Optional[Any] = None) -> jax.Array:
        """``a @ b`` with the K contraction scheduled per this strategy.

        ``a: (..., M, K)`` (leading batch dims allowed), ``b: (K, N)``.
        ``out_dtype`` defaults to ``a.dtype`` for floats and int32 for
        integer operands (an int8 output would silently wrap).
        """

    @abc.abstractmethod
    def cost(self, n_operands: int, dtype: Any = "bfloat16") -> Dict[str, Any]:
        """Analytic cost of one ``n_operands``-wide reduction.

        Returns a :class:`repro.launch.costing.CellCost`-compatible dict:

          flops                 per output element (mults + scheduled adds)
          hbm_bytes             operand bytes streamed per output element
          adds                  two-operand additions per output
          ops_per_add           hardware ops each add costs (LOA: ~6 on VPU)
          sequential_steps      scan/grid trip count (tree: 1)
          working_set_operands  live operands per sequential step
          exact                 True when the reduction is exact up to
                                reassociation
        """

    # ---- shared jnp/pallas shape plumbing ---------------------------------
    @staticmethod
    def _flatten_dot(a: jax.Array):
        """``(..., M, K) -> (rows, K)`` + a restorer for the output."""
        lead = a.shape[:-1]
        a2 = a.reshape((-1, a.shape[-1]))
        return a2, (lambda y: y.reshape(lead + (y.shape[-1],)))

    @staticmethod
    def _flatten_sum(x: jax.Array, axis: int):
        """``x`` with ``axis`` moved to front and trailing dims flattened to
        ``(n, f)``; returns the 2-D view + a restorer for the reduced output."""
        x = jnp.moveaxis(jnp.asarray(x), axis, 0)
        rest = x.shape[1:]
        x2 = x.reshape((x.shape[0], -1))
        return x2, (lambda y: y.reshape(rest))

    @staticmethod
    def _default_out_dtype(a_dtype, out_dtype):
        if out_dtype is not None:
            return jnp.dtype(out_dtype)
        if jnp.issubdtype(jnp.dtype(a_dtype), jnp.integer):
            return jnp.dtype(jnp.int32)
        return jnp.dtype(a_dtype)
