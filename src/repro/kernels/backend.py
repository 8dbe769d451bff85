"""The one interpret-or-compile switch of every Pallas kernel entry point."""

from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` as given; ``None`` decides by backend: compiled
    Mosaic kernels on TPU, the Pallas interpreter everywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
