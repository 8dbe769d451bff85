"""Do the benchmark's training programs fit one TPU v5e chip?

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/fit_v5e.py [config ...]

Compiles, for one described (not attached) v5e chip, the in-step fused
training program that ``train(donate, fused_detect, parity,
canary_slices=1)`` runs: the step, the K=1 canary check of the input
state and arm of the output state over one packing buffer, and the
incremental XOR parity update, with the state, packing buffer, tables and
parity donated as the program donates them.  It prints
``memory_analysis()`` for each configuration file and batch shape named
in the cell files that use it.  Nothing runs, so no time is reported.
Not a test: a compile takes tens of seconds and loads the TPU library.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import cells  # noqa: E402

GIB = 2.0 ** 30


def fused_program(cfg, batch: int, seq: int, one_chip):
    """The lowered in-step fused step at (batch, seq), as FusedStepFactory
    builds it for canary K=1 with parity."""
    from repro.core.parity import parity_plan_for
    from repro.kernels import checksum as ck
    from repro.kernels import digest as kdigest
    from repro.launch.specs import batch_struct, state_struct
    from repro.train.loop import make_train_step

    def on(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    state = on(state_struct(cfg, batch))
    plan = kdigest.plan_for(state)
    pplan = parity_plan_for(state)
    idx = list(range(plan.n_leaves))
    core, union = kdigest.check_arm_subcomputation(plan, idx, idx)
    n_rows = sum(plan.specs[i].n_rows for i in union)
    buf = jax.ShapeDtypeStruct(
        (-(-n_rows // ck.TILE_ROWS) * ck.TILE_ROWS * ck.LANES,), jnp.int32,
        sharding=one_chip)
    table = jax.ShapeDtypeStruct((plan.n_leaves, 2), jnp.uint32,
                                 sharding=one_chip)
    parity = jax.ShapeDtypeStruct(pplan.buffer_shape, jnp.int32,
                                  sharding=one_chip)
    step_fn = make_train_step(cfg, global_batch=batch)

    def fused(state, buf, ref_read, ref_write, parity, b):
        in_leaves = plan.leaves(state)
        p_old = pplan.leaves(state)
        new_state, aux = step_fn(state, b)
        out_leaves = plan.leaves(new_state)
        buf, flag, bad, new_write = core(buf, in_leaves + out_leaves,
                                         ref_read, ref_write)
        new_parity = pplan.update_leaves(parity, p_old,
                                         pplan.leaves(new_state), flag)
        return new_state, aux, buf, flag, bad, new_write, new_parity

    b = on(batch_struct(cfg, batch, seq))
    return jax.jit(fused, donate_argnums=(0, 1, 3, 4)).lower(
        state, buf, table, table, parity, b)


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    # kernels pick interpret mode from the default backend, which here is
    # the CPU; the programs below are for the described chip
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    spec = cells.load_benchmark()
    names = argv or sorted({w["config"] for w in spec["workloads"]})
    for name in names:
        cfg = cells.arch_config(cells.load_config(name))
        shapes = sorted({(t["global_batch"], t["seq_len"])
                         for w in spec["workloads"] if w["config"] == name
                         for t in [cells.load_traffic(w["traffic"])]
                         if t["kind"] == "train"})
        for batch, seq in shapes:
            ma = fused_program(cfg, batch, seq, one_chip).compile() \
                .memory_analysis()
            args = ma.argument_size_in_bytes
            temps = ma.temp_size_in_bytes
            print(json.dumps({
                "config": name, "batch": batch, "seq": seq,
                "arguments_gib": args / GIB, "temporaries_gib": temps / GIB,
                "outputs_gib": ma.output_size_in_bytes / GIB,
                "aliased_gib": ma.alias_size_in_bytes / GIB,
                "total_gib": (args + temps) / GIB}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
