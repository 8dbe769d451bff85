"""Pure-step replay — the RSI (Recoverable Sequence of Instructions) rung.

The paper replays a cloned address computation over *terminal values* that
are still intact in the process image.  The training-loop analogue observes
that the whole step function is pure:

    state_t = step(state_{t-1}, batch(t-1)),   batch(t) = f(seed, t)

so given any *verified* snapshot at step t0 <= t, the exact state at t is
recomputable by replaying (t - t0) deterministic steps — no I/O, no lost
work beyond the replayed window, bit-exact when each step runs the same
compiled program as the run being recovered.  XLA promises identical bits
only for one executable: on TPU a step compiled alone and the same step
compiled inside the in-step fused canary program round differently, so
the replay must be handed the hot path's own executables.

The snapshot plays the paper's "terminal values" role: the micro-checkpointer
guarantees (by digest verification — our liveness analysis) that the replay
inputs are intact before we trust them.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro import obs


@dataclass
class ReplayResult:
    state: object
    steps_replayed: int
    from_step: int
    to_step: int


def device_put_like(host_state, like_state=None, shardings=None):
    """Move a host snapshot back to device buffers.

    ``like_state`` shards each leaf like the live reference; ``shardings``
    (a pytree of shardings) serves the donated-mesh case where no live
    reference exists — the snapshot upload itself is shard-local: every
    device receives only its addressable slice of each leaf, never a full
    replicated copy (DESIGN.md §5)."""
    if like_state is None and shardings is not None:
        return jax.device_put(host_state, shardings)
    if like_state is None:
        return jax.tree_util.tree_map(jax.numpy.asarray, host_state)

    def put(host_leaf, live_leaf):
        try:
            sharding = live_leaf.sharding
        except AttributeError:
            sharding = None
        if sharding is not None:
            return jax.device_put(host_leaf, sharding)
        return jax.numpy.asarray(host_leaf)

    return jax.tree_util.tree_map(put, host_state, like_state)


def replay(step_at: Callable, batch_fn: Callable, snapshot_state,
           from_step: int, to_step: int, *, like_state=None,
           shardings=None, on_step: Optional[Callable] = None
           ) -> ReplayResult:
    """Replay steps ``from_step`` (the snapshot was taken *before*
    executing it) up to (but not including) ``to_step``.

    step_at(step, state, batch) -> (state, metrics) runs step ``step`` with
    the program the recovered run used for it; batch_fn(step) -> batch.
    ``shardings`` places the snapshot on a mesh when no ``like_state``
    reference survives (donated loops).
    """
    assert to_step >= from_step, (from_step, to_step)
    nbytes = sum(leaf.nbytes
                 for leaf in jax.tree_util.tree_leaves(snapshot_state))
    with obs.span("snapshot.upload", bytes=nbytes):
        state = device_put_like(snapshot_state, like_state, shardings)
        # timed to the upload's end, not its enqueue
        jax.block_until_ready(state)
    for s in range(from_step, to_step):
        state, _ = step_at(s, state, batch_fn(s))
        if on_step is not None:
            on_step(s, state)
    return ReplayResult(state=state, steps_replayed=to_step - from_step,
                        from_step=from_step, to_step=to_step)
