"""Fault injection harness — reproduces the paper's §5.1 methodology on this
framework's failure domain.

Paper: pick a dynamic instruction weighted by execution count, flip one bit
in its destination operand, observe the outcome (Benign / Crash / SDC /
Hang) and the manifestation latency.

Here: pick a train-state leaf weighted by element count (the execution-
weighted analogue — large tensors are touched proportionally more), flip one
bit of one element at a chosen step, and classify the outcome by running the
instrumented loop:
  * Benign  — detectors stay silent AND the final state matches fault-free
              (e.g. flip of a dead mantissa bit, or masked by the optimizer)
  * Crash   — a trap fires (non-finite loss / checksum mismatch): the
              TPU-domain analogue of SIGSEGV; recovery is attempted
  * SDC     — no trap, but the trajectory diverges from fault-free
  * Hang    — loss stops improving for a window (proxy; true hangs do not
              occur in a pure dataflow program)
"""

from __future__ import annotations

import random
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as kref
from repro.kernels.ops import leaf_key


@dataclass(frozen=True)
class InjectionPlan:
    leaf: str          # leaf path key
    element: int       # flat element index
    bit: int           # bit position within the element's width
    step: int          # training step at which to inject
    target: str = "params"  # 'params' | 'opt' | 'iv' | 'activations'


def _leaf_catalog(tree) -> List[Tuple[str, int, str]]:
    """[(key, size, dtype_name)] for every array leaf — from shapes and
    dtypes only: no leaf leaves the device."""
    out = []

    def visit(path, leaf):
        out.append((leaf_key(path),
                    int(np.prod(jnp.shape(leaf), dtype=np.int64)),
                    str(jnp.result_type(leaf))))
        return leaf

    jax.tree_util.tree_map_with_path(visit, tree)
    return out


def sample_plan(rng: random.Random, state, max_step: int,
                target: str = "params") -> InjectionPlan:
    """Size-weighted leaf choice; uniform element/bit/step — the paper's
    execution-weighted single-bit-flip model."""
    tree = state[target] if target in ("params", "opt", "iv") else state
    catalog = _leaf_catalog(tree)
    sizes = [s for (_, s, _) in catalog]
    total = sum(sizes)
    pick = rng.randrange(total)
    acc = 0
    for key, size, dtype in catalog:
        acc += size
        if pick < acc:
            width = {"float32": 32, "int32": 32, "uint32": 32,
                     "bfloat16": 16, "float16": 16, "int16": 16,
                     "int8": 8, "uint8": 8}.get(dtype, 32)
            return InjectionPlan(
                leaf=key,
                element=rng.randrange(size),
                bit=rng.randrange(width),
                step=rng.randrange(max_step),
                target=target,
            )
    raise AssertionError("unreachable")


def _signed_mask(bit: int, width: int):
    """1<<bit as a signed value of ``width`` bits (wraps the sign bit)."""
    return int(np.uint64(1 << bit).astype({32: np.int32, 16: np.int16,
                                           8: np.int8}[width]))


def flip_bit(arr: jnp.ndarray, element: int, bit: int) -> jnp.ndarray:
    """Flip one bit of one element, preserving dtype/shape (pure)."""
    a = jnp.asarray(arr)
    shape, dtype = a.shape, a.dtype
    if dtype in (jnp.float32, jnp.uint32):
        i = jax.lax.bitcast_convert_type(a, jnp.int32).reshape(-1)
        i = i.at[element].set(i[element] ^ jnp.int32(_signed_mask(bit, 32)))
        return jax.lax.bitcast_convert_type(i.reshape(shape), dtype)
    if dtype == jnp.int32:
        f = a.reshape(-1)
        f = f.at[element].set(f[element] ^ jnp.int32(_signed_mask(bit, 32)))
        return f.reshape(shape)
    if dtype in (jnp.bfloat16, jnp.float16, jnp.int16):
        i = jax.lax.bitcast_convert_type(a.reshape(-1), jnp.int16)
        i = i.at[element].set(
            i[element] ^ jnp.int16(_signed_mask(min(bit, 15), 16)))
        return jax.lax.bitcast_convert_type(i, dtype).reshape(shape)
    if dtype in (jnp.int8, jnp.uint8):
        f = a.reshape(-1)
        f = f.at[element].set(
            f[element] ^ jnp.asarray(_signed_mask(min(bit, 7), 8), dtype))
        return f.reshape(shape)
    raise TypeError(f"unsupported dtype {dtype}")


def inject(state, plan: InjectionPlan):
    """Apply the plan to a train state (returns a new state)."""
    if plan.target in ("params", "opt", "iv"):
        tree = state[plan.target]
        out = dict(state)
        out[plan.target] = _inject_tree(tree, plan)
        return out
    return _inject_tree(state, plan)  # plan sampled over the whole tree


def _inject_tree(tree, plan: InjectionPlan):
    hit = {"done": False}

    def visit(path, leaf):
        if leaf_key(path) == plan.leaf and not hit["done"]:
            hit["done"] = True
            flipped = flip_bit(leaf, plan.element, plan.bit)
            # mesh state: the flip's bitcast/reshape chain must not change
            # the leaf's layout — an adversary corrupts bytes in place, it
            # does not reshard the victim
            sharding = getattr(leaf, "sharding", None)
            if isinstance(sharding, jax.sharding.NamedSharding):
                flipped = jax.device_put(flipped, sharding)
            return flipped
        return leaf

    out = jax.tree_util.tree_map_with_path(visit, tree)
    if not hit["done"]:
        raise KeyError(f"leaf not found: {plan.leaf}")
    return out


def inject_shard_loss(state, leaf_frac: float, rng: random.Random,
                      target: str = "params"):
    """Simulate a lost device: NaN-out a contiguous fraction of every leaf
    of the target tree (the shard that lived on the dead chip)."""
    def visit(path, leaf):
        arr = jnp.asarray(leaf)
        if not jnp.issubdtype(arr.dtype, jnp.floating):
            return leaf
        n = arr.size
        k = max(1, int(n * leaf_frac))
        start = rng.randrange(max(n - k, 1))
        flat = arr.reshape(-1)
        flat = flat.at[start:start + k].set(jnp.nan)
        return flat.reshape(arr.shape)

    out = dict(state)
    out[target] = jax.tree_util.tree_map_with_path(visit, state[target])
    return out
