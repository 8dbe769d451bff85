"""Disk checkpointing — the classic C/R baseline the paper measures against,
built properly so the comparison is fair:

* **async**: serialisation happens on a background thread off the step path
  (the step only pays one device->host copy);
* **double-buffered**: writes alternate between two slots and commit by
  atomic manifest rename — a crash mid-write never destroys the previous
  good checkpoint;
* **digest-verified**: every leaf's Fletcher digest is stored in the
  manifest and re-checked on load (a rotted checkpoint must not silently
  restore — the same exact-or-abort rule the recovery ladder uses).

Format: one ``.npz`` per slot (leaf-path keys) + ``manifest.json``
(step, slot, digests, dtypes).  bfloat16 leaves are stored as uint16 views
(npz has no bf16) and restored bit-exactly.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops

_MANIFEST = "manifest.json"


# ---------------------------------------------------------------------------
# (de)serialisation helpers
# ---------------------------------------------------------------------------

def _flatten(state) -> Dict[str, np.ndarray]:
    out: Dict[str, np.ndarray] = {}

    def visit(path, leaf):
        out[kops.leaf_key(path)] = np.asarray(leaf)
        return leaf

    jax.tree_util.tree_map_with_path(visit, state)
    return out


def _store_view(arr: np.ndarray) -> Tuple[np.ndarray, str]:
    """npz-compatible view + the original dtype name."""
    dt = str(arr.dtype)
    if dt == "bfloat16":
        return arr.view(np.uint16), dt
    return arr, dt


def _restore_view(arr: np.ndarray, dtype: str) -> np.ndarray:
    if dtype == "bfloat16":
        return arr.view(jnp.bfloat16.dtype)
    return arr


def _unflatten(like_state, leaves: Dict[str, np.ndarray]):
    def visit(path, leaf):
        key = kops.leaf_key(path)
        arr = leaves[key]
        return arr.reshape(np.shape(leaf))

    return jax.tree_util.tree_map_with_path(visit, like_state)


# ---------------------------------------------------------------------------
# save / load
# ---------------------------------------------------------------------------

def save_checkpoint(directory: str, state, step: int, *, slot: int = 0) -> str:
    """Write ``state`` into ``directory/slot{slot}.npz`` and commit the
    manifest atomically.  Returns the manifest path."""
    os.makedirs(directory, exist_ok=True)
    flat = _flatten(state)
    digests = {k: [int(x) for x in np.asarray(kops.checksum(v))]
               for k, v in flat.items()}
    views, dtypes = {}, {}
    for k, v in flat.items():
        view, dt = _store_view(v)
        views[k] = view
        dtypes[k] = dt

    payload = os.path.join(directory, f"slot{slot}.npz")
    tmp = payload + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **views)
    os.replace(tmp, payload)

    manifest = {
        "step": int(step),
        "slot": int(slot),
        "payload": os.path.basename(payload),
        "wall": time.time(),
        "digests": digests,
        "dtypes": dtypes,
    }
    mpath = os.path.join(directory, _MANIFEST)
    fd, tmpm = tempfile.mkstemp(dir=directory, suffix=".json.tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(manifest, f)
    os.replace(tmpm, mpath)   # atomic commit: manifest names the good slot
    return mpath


def load_checkpoint(directory: str, like_state, *, verify: bool = True):
    """Load the committed checkpoint. Returns (state, step).

    Raises ``ValueError`` if digest verification fails (exact-or-abort).
    """
    mpath = os.path.join(directory, _MANIFEST)
    with open(mpath) as f:
        manifest = json.load(f)
    payload = os.path.join(directory, manifest["payload"])
    with np.load(payload) as z:
        leaves = {k: _restore_view(z[k], manifest["dtypes"][k])
                  for k in z.files}
    if verify:
        bad = [k for k, d in manifest["digests"].items()
               if not np.array_equal(
                   np.asarray(kops.checksum(leaves[k])), np.asarray(d))]
        if bad:
            raise ValueError(f"checkpoint digest mismatch: {bad[:4]}")
    state = _unflatten(like_state, leaves)
    return state, int(manifest["step"])


def load_latest(directory: str, like_state, *, verify: bool = True):
    return load_checkpoint(directory, like_state, verify=verify)


# ---------------------------------------------------------------------------
# manager
# ---------------------------------------------------------------------------

@dataclass
class _Pending:
    step: int
    host_state: object


class CheckpointManager:
    """Async double-buffered checkpointer.

    The step path pays only ``jax.device_get`` (one D2H copy); npz encoding
    and fsync happen on the writer thread.  Slots alternate 0/1 so the
    previous checkpoint survives until the new manifest commits.
    """

    def __init__(self, directory: str, interval: int = 100, *,
                 async_write: bool = True):
        self.directory = directory
        self.interval = max(1, interval)
        self.async_write = async_write
        self._slot = 0
        self._thread: Optional[threading.Thread] = None
        self._last_error: Optional[BaseException] = None
        self.saves = 0
        os.makedirs(directory, exist_ok=True)

    # -- step-path API ----------------------------------------------------

    def maybe_save(self, step: int, state) -> bool:
        if step % self.interval != 0:
            return False
        self.save(step, state)
        return True

    def save(self, step: int, state) -> None:
        from repro.core.microcheckpoint import host_copy

        # donation-safe D2H: a zero-copy host view would pin the live
        # buffers against donate_argnums for as long as the async writer
        # holds them — host_copy materialises real copies
        host = host_copy(state)
        self.wait()                                        # 1-deep pipeline
        if self.async_write:
            self._thread = threading.Thread(
                target=self._write, args=(step, host), daemon=True)
            self._thread.start()
        else:
            self._write(step, host)
        self.saves += 1

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._last_error is not None:
            err, self._last_error = self._last_error, None
            raise err

    # -- restore API --------------------------------------------------------

    def restore(self, like_state):
        self.wait()
        return load_latest(self.directory, like_state)

    def loader(self, like_state):
        """A zero-arg callable for RecoveryRuntime(checkpoint=...)."""
        return lambda: self.restore(like_state)

    # -- writer thread ------------------------------------------------------

    def _write(self, step: int, host_state) -> None:
        try:
            slot = self._slot
            self._slot ^= 1
            save_checkpoint(self.directory, host_state, step, slot=slot)
        except BaseException as e:  # surfaced on next wait()
            self._last_error = e
