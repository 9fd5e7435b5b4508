"""Production mesh construction.

A FUNCTION, not a module constant — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init; everything
else sees the real single-device CPU).

Topology model (TPU v5e): one pod = 16×16 = 256 chips; ``multi_pod`` adds a
leading ``pod`` axis across 2 pods (512 chips) connected by DCI. Axis use:

  pod    — outer data parallelism (gradient reduction crosses pods once)
  data   — data parallelism + FSDP parameter sharding (intra-pod ICI)
  model  — tensor/expert parallelism (highest-bandwidth dimension)
"""

from __future__ import annotations

import math
import os
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "parse_mesh",
           "ensure_host_devices", "SINGLE_POD", "MULTI_POD"]

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...],
              axes: Optional[Tuple[str, ...]] = None):
    """Arbitrary (test-sized) mesh: shape (d, m) or (p, d, m)."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):] if len(shape) == 3 \
            else ("data", "model")[-len(shape):]
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    # Auto axes: the model code places arrays with
    # ``with_sharding_constraint``, which refuses Explicit mesh axes
    # (``jax.make_mesh``'s default)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def parse_mesh(spec: str) -> Tuple[int, ...]:
    """CLI mesh spec ``"DxM"`` (or ``"PxDxM"``) → shape tuple.

    ``"2x4"`` → ``(data=2, model=4)``; ``"2x2x2"`` adds a leading ``pod``
    axis. Every factor must be a positive integer.
    """
    try:
        shape = tuple(int(p) for p in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"bad mesh spec {spec!r}: expected DxM like '2x4'")
    if len(shape) not in (2, 3) or any(s < 1 for s in shape):
        raise ValueError(f"bad mesh spec {spec!r}: expected 2 or 3 positive "
                         "factors (data x model, optionally pod-leading)")
    return shape


def ensure_host_devices(shape) -> None:
    """Request enough XLA host-platform devices for a CPU run.

    ``shape`` is a mesh shape tuple (``parse_mesh`` output) or a bare
    device count. Must be called before jax initializes its backends
    (first device or array op) — XLA locks the device count at first
    init. Appends ``--xla_force_host_platform_device_count`` to
    ``XLA_FLAGS`` unless the flag is already set, so an explicit
    environment always wins; on real accelerator platforms the flag is
    inert.
    """
    n = shape if isinstance(shape, int) else math.prod(shape)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = \
        f"{flags} --xla_force_host_platform_device_count={n}".strip()
