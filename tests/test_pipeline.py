"""Pipeline parallelism: the GPipe schedule must equal the sequential
composition of stages, for any (stages, microbatches) combination.
Runs on a subprocess mesh (the test session keeps 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


PIPE_PROG = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.distributed.pipeline import pipeline_apply
    from repro.launch.mesh import make_mesh

    S, M, B, d = 4, 6, 2, 8
    mesh = make_mesh((S,), ("stage",))
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, S)
    params = {"w": jnp.stack([
        jax.random.normal(k, (d, d)) * 0.3 for k in ks]),
        "b": jnp.stack([jax.random.normal(k, (d,)) * 0.1 for k in ks])}
    xs = jax.random.normal(jax.random.fold_in(key, 9), (M, B, d))

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    # sequential truth
    y_ref = xs
    for i in range(S):
        y_ref = jax.vmap(lambda x: stage_fn(
            {"w": params["w"][i], "b": params["b"][i]}, x))(y_ref)

    with mesh:
        y = pipeline_apply(stage_fn, params, xs, mesh, axis="stage")
    err = float(jnp.max(jnp.abs(y - y_ref)))
    print(json.dumps({"err": err}))
""")


def test_gpipe_matches_sequential():
    out = subprocess.run([sys.executable, "-c", PIPE_PROG],
                         capture_output=True, text=True, cwd=REPO,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["err"] < 1e-5, data
