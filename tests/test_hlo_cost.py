"""The trip-count-aware HLO cost analyzer vs analytic ground truth.

Multi-device cases run in a subprocess (XLA device count is locked at
first jax init; the test session must keep seeing 1 CPU device).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.launch import hlo_cost as HC

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_single_device_matmul_flops():
    M, K, N = 64, 32, 48
    c = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.float32)).compile()
    cost = HC.analyze(c.as_text())
    assert cost.flops == 2 * M * K * N


def test_scan_trip_count_multiplies():
    M, K, T = 32, 16, 9

    def g(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=T)
        return y

    c = jax.jit(g).lower(
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, K), jnp.float32)).compile()
    cost = HC.analyze(c.as_text())
    assert cost.flops == 2 * M * K * K * T
    assert T in cost.while_trips.values()


SUBPROCESS_PROG = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import hlo_cost as HC
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    M, K, N = 512, 256, 1024
    a = jax.ShapeDtypeStruct((M, K), jnp.float32)
    b = jax.ShapeDtypeStruct((K, N), jnp.float32)
    with mesh:
        c = jax.jit(lambda a, b: a @ b, in_shardings=(
            NamedSharding(mesh, P("data", None)),
            NamedSharding(mesh, P(None, "model")))).lower(a, b).compile()
    cost = HC.analyze(c.as_text())

    def h(x):
        y = (x @ x.T).sum(0)
        return jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, P(None)))
    with mesh:
        c2 = jax.jit(h, in_shardings=(NamedSharding(mesh, P("data", "model")),)
                     ).lower(jax.ShapeDtypeStruct((M, M), jnp.float32)).compile()
    cost2 = HC.analyze(c2.as_text())
    print(json.dumps({
        "flops_per_dev": cost.flops,
        "expected": 2 * M * K * N / 8,
        "coll_kinds": sorted(cost2.coll_bytes_by_kind),
        "coll_total": cost2.coll_bytes,
    }))
""")


def test_spmd_per_device_flops_and_collectives():
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_PROG],
                         capture_output=True, text=True, cwd=REPO,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["flops_per_dev"] == data["expected"]
    assert "all-reduce" in data["coll_kinds"]
    assert data["coll_total"] > 0


def test_collective_seconds_algo_factors():
    t = HC.collective_seconds({"all-reduce": 100e9, "all-gather": 50e9},
                              link_bw=50e9)
    assert abs(t - (2 * 100e9 + 50e9) / 50e9 / 1) < 1e-9
