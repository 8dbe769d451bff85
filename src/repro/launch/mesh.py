"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — smoke tests and benches must keep seeing
1 CPU device; only the dry-run process forces 512 placeholder devices.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod = 16x16 = 256 chips (v5e pod, ("data","model")); two pods
    add a leading "pod" axis (DCN) => 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """THE mesh constructor of this repo (tests and benchmarks included).

    Every axis is ``AxisType.Auto``: the sharding code places arrays with
    ``NamedSharding``/``with_sharding_constraint`` and lets GSPMD propagate
    the rest.  ``jax.make_mesh`` defaults to Explicit axes, under which
    jitted code outside a ``jax.set_mesh`` context refuses those
    placements."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def parse_mesh(spec: Optional[str]):
    """``--mesh`` strings to (shape, axes): "4" -> data-parallel only,
    "4,2" -> ("data", "model"), "2,4,2" -> ("pod", "data", "model")."""
    if not spec:
        return None, None
    shape = tuple(int(s) for s in spec.replace("x", ",").split(",") if s)
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}.get(len(shape))
    if axes is None:
        raise ValueError(f"--mesh takes 1-3 comma-separated sizes, got {spec!r}")
    return shape, axes


def make_context(mesh_spec: Optional[str]):
    """DistContext for a ``--mesh`` knob (None off-mesh) — the shared
    entry point of the train/serve drivers' mesh flags.  On CPU, force
    devices first: XLA_FLAGS=--xla_force_host_platform_device_count=N."""
    shape, axes = parse_mesh(mesh_spec)
    if shape is None:
        return None
    need = int(np.prod(shape))
    have = len(jax.devices())
    if have < need:
        raise ValueError(
            f"--mesh {mesh_spec} needs {need} devices, have {have} — on "
            f"CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need}")
    from repro.distributed.context import DistContext
    return DistContext.for_mesh(make_mesh(shape, axes))


def make_degraded_mesh(lost_data_slices: int = 1, *, multi_pod: bool = False,
                       base=None, dead=None):
    """Elastic re-mesh after losing rows of the data axis (a failed
    host/board takes out a whole model row).  The job continues at
    reduced data-parallel width on the surviving devices — no replacement
    hardware required.

    With ``base`` (a live Mesh), the degraded mesh is the SAME axis names
    over the base's device array with the dead data rows deleted —
    ``dead`` gives explicit row indices (default: the trailing
    ``lost_data_slices`` rows).  Without ``base``, the original
    production-shape path: a fresh (16-lost)x16 (or 31x16 multi-pod)
    mesh over the leading devices."""
    from jax.sharding import Mesh
    if base is not None:
        names = base.axis_names
        axis = "data" if "data" in names else names[0]
        ai = names.index(axis)
        n = base.devices.shape[ai]
        rows_dead = set(int(r) for r in dead) if dead is not None else \
            set(range(n - lost_data_slices, n))
        keep = [r for r in range(n) if r not in rows_dead]
        if not keep:
            raise ValueError("no data slices left")
        return Mesh(np.take(base.devices, keep, axis=ai), names)
    rows = (32 if multi_pod else 16) - lost_data_slices
    if rows < 1:
        raise ValueError("no data slices left")
    devices = np.asarray(jax.devices()[: rows * 16]).reshape(rows, 16)
    return Mesh(devices, ("data", "model"))


def mesh_chip_count(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
