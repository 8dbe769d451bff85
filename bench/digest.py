"""Host Fletcher digest of raw bytes: the oracle for the chip's canary.

The canary digests every leaf as a flat stream of 32-bit words: 4-byte
elements as their bits, 2- and 1-byte elements zero-extended, and with
``s1 = sum(w)`` and ``s2 = sum((i + 1) * w)`` over word index ``i``,
both modulo 2**32.  A copy of that published rule, so that the oracle is
not the code it checks.
"""

from __future__ import annotations

from typing import Dict

import jax
import numpy as np


def words(x: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(x).reshape(-1)
    size = a.dtype.itemsize
    if size == 4:
        return a.view(np.uint32)
    if size == 2:
        return a.view(np.uint16).astype(np.uint32)
    if size == 1:
        return a.view(np.uint8).astype(np.uint32)
    raise ValueError(f"no digest rule for {a.dtype}")


def fletcher(x: np.ndarray) -> np.ndarray:
    w = words(x)
    idx = np.arange(1, w.shape[0] + 1, dtype=np.uint32)
    return np.array([np.add.reduce(w, dtype=np.uint32),
                     np.add.reduce(w * idx, dtype=np.uint32)],
                    dtype=np.uint32)


def _key(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def tree_digests(tree) -> Dict[str, np.ndarray]:
    """Digest of every leaf of a host tree, keyed ``a/b/0/c``."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {_key(p): fletcher(np.asarray(leaf)) for p, leaf in flat}
