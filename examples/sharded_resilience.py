"""Mesh-sharded resilience end to end (DESIGN.md §5), on a forced
8-device CPU mesh:

    PYTHONPATH=src python examples/sharded_resilience.py

1. shard a smoke train state over a 4x2 ("data", "model") mesh,
2. run the shard-local rotating canary (one logical launch + ONE
   all-reduced scalar per step — the only cross-device traffic),
3. flip one bit in one device's shard of one weight,
4. detect it and attribute it to the exact (leaf, shard) pair,
5. restore ONLY the injured shard's bytes from a version-matched,
   digest-certified micro-snapshot — healthy shards keep their buffers —
   and prove the repaired state is bit-identical to the truth.
"""

import os

# must be set before jax initialises its backends
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.detect import ChecksumCanary
from repro.core.faults import InjectionPlan, inject
from repro.core.icp import promote
from repro.core.microcheckpoint import MicroCheckpointer
from repro.core.recover import RecoveryRuntime
from repro.data.pipeline import TokenPipeline
from repro.distributed.context import DistContext
from repro.kernels import digest as kdigest
from repro.launch.mesh import make_mesh
from repro.launch.specs import bind_state
from repro.train.loop import (
    make_train_state,
    make_train_step,
)


def main():
    assert len(jax.devices()) >= 8, (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8")
    cfg = get_config("iterpro-100m").smoke()
    B, S = 8, 32
    mesh = make_mesh((4, 2), ("data", "model"))
    ctx = DistContext.for_mesh(mesh)
    print(f"mesh: {dict(mesh.shape)} -> {ctx.n_devices} shards")

    pipe = TokenPipeline(cfg.model.vocab_size, S, B, seed=0)
    state = make_train_state(cfg, jax.random.PRNGKey(0), global_batch=B)
    state, pinned, bfn, shardings = bind_state(
        ctx, cfg, state, make_train_step(cfg, global_batch=B),
        lambda s: pipe.batch_at(s))
    step = jax.jit(pinned)

    micro = MicroCheckpointer(interval=2, ctx=ctx)
    canary = ChecksumCanary(state, n_slices=1, ctx=ctx)
    runtime = RecoveryRuntime(step_fn=step, batch_fn=bfn,
                              iv_registry=promote(cfg, B), micro=micro,
                              shardings=shardings)

    print("training 4 clean steps (canary: 1 launch + 1 all-reduced "
          "scalar sync/step)...")
    for s in range(4):
        micro.maybe_snapshot(s, state)
        kdigest.STATS.reset()
        new_state, m = step(state, bfn(s))
        assert canary.check_and_arm(s, state, new_state) is None
        l, sy, tr = kdigest.STATS.snapshot()
        print(f"  step {s}: loss {float(m['loss']):.4f}  "
              f"canary launches={l} syncs={sy} retraces={tr}")
        state = new_state
    micro.maybe_snapshot(4, state)                 # version-matched anchor
    truth = jax.tree_util.tree_map(np.asarray, state)

    leaf_key = "groups/0/0/ffn/up/w"
    print(f"\nflipping bit 30 of params/{leaf_key}[1000] "
          f"(lands in the model-axis-1 shards)...")
    bad = inject(state, InjectionPlan(leaf_key, 1000, 30, 0, "params"))

    new_state, m = step(bad, bfn(4))
    report = canary.check_and_arm(4, bad, new_state)
    assert report is not None
    print(f"detected: {report}")
    print(f"(leaf, shard) attribution: {report.shards}")

    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(truth))
    fixed, ev = runtime.recover(bad, report, 4)
    print(f"\nrecovered via rung '{ev.rung}' in {ev.wall_seconds*1e3:.1f} "
          f"ms — moved {ev.bytes_moved} B of a {state_bytes} B state "
          f"({100 * ev.bytes_moved / state_bytes:.2f}%)")
    ok = all(np.array_equal(np.asarray(a), b)
             for a, b in zip(jax.tree_util.tree_leaves(fixed),
                             jax.tree_util.tree_leaves(truth)))
    print(f"repaired state bit-identical to pre-fault truth: {ok}")
    assert ok and ev.rung == "shard_patch"


if __name__ == "__main__":
    main()
