"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain 512 placeholder host devices (dryrun.py line 1-2).

Mesh layout (DESIGN.md Sec. 5):
  single-pod:  (16, 16)        ("data", "model")
  multi-pod:   (2, 16, 16)     ("pod", "data", "model")
The "pod" axis is pure data parallelism whose gradient all-reduce is the
only cross-pod (DCN) collective; "data" carries DP + FSDP (ZeRO-3
parameter/optimizer sharding); "model" carries TP / EP / monarch block
parallelism within an ICI domain.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int) -> tuple:
    """GSPMD-propagated ("Auto") axes: the partitioner places every
    intermediate from the parameter/pool shardings plus the ``logical()``
    constraints.  ``jax.make_mesh`` defaults to Explicit axes, under which
    gathers such as the embedding lookup need per-op output shardings."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(*, model: int | None = None,
                   data: int | None = None) -> jax.sharding.Mesh:
    """``("data", "model")`` mesh over the visible host devices.

    With no arguments, picks a sensible default over ALL visible devices
    (``model=2`` when >=4 devices, else ``model=1``) — it never silently
    drops devices the way the old ``(1, 1)`` fallback did.  Explicit
    ``model=`` / ``data=`` override the axis sizes (the olmax trick,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, is how CI gets
    more than one host device); an axis that does not divide the device
    count is an error, not a silent reshape.
    """
    n = len(jax.devices())
    if model is None and data is None:
        model = 2 if n >= 4 else 1
    if model is not None:
        if n % model != 0:
            raise ValueError(
                f"model={model} does not divide the {n} visible devices "
                f"(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                f"to fake more host devices)")
        if data is None:
            data = n // model
    else:  # data given, model not
        if n % data != 0:
            raise ValueError(
                f"data={data} does not divide the {n} visible devices")
        model = n // data
    if model * data != n:
        raise ValueError(
            f"mesh ({data} data x {model} model) = {model * data} devices, "
            f"but {n} are visible — axes must multiply to the device count")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


__all__ = ["make_production_mesh", "make_host_mesh"]
