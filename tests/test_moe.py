"""MoE capacity dispatch: equivalence with per-token dense expert selection
when capacity is ample; EP path equivalence on a multi-device subprocess."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import moe as M

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(E=4, k=2, d=16, ff=32, cap=1.25):
    return ModelConfig(family="moe", n_layers=1, d_model=d, n_heads=2,
                       n_kv_heads=2, d_ff=ff, vocab_size=64, n_experts=E,
                       top_k=k, moe_d_ff=ff, moe_capacity=cap,
                       param_dtype="float32", compute_dtype="float32")


def _dense_oracle(x, p, cfg):
    """Per-token dense computation of the selected experts (no capacity)."""
    w, ids, _ = M._route(x.astype(jnp.float32), p["router"]["w"], cfg.top_k)
    outs = []
    for t in range(x.shape[0]):
        acc = jnp.zeros((cfg.d_model,), jnp.float32)
        for j in range(cfg.top_k):
            e = int(ids[t, j])
            h = x[t] @ p["gate"][e], x[t] @ p["up"][e]
            hh = jax.nn.silu(h[0].astype(jnp.float32)) * h[1].astype(jnp.float32)
            acc = acc + w[t, j] * (hh.astype(x.dtype) @ p["down"][e]).astype(jnp.float32)
        outs.append(acc)
    return jnp.stack(outs).astype(x.dtype)


def test_capacity_dispatch_matches_dense_oracle():
    cfg = _cfg(cap=8.0)   # ample capacity: zero drops -> exact equivalence
    key = jax.random.PRNGKey(0)
    p = M.moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (24, cfg.d_model))
    y, aux = M._moe_local_math(x, p, cfg)
    y_ref = _dense_oracle(x, p, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    assert float(aux["lb_loss"]) > 0


def test_capacity_drops_overflow_tokens():
    """With capacity 8 and all tokens routed to one expert, the overflow
    contributes zero (GShard semantics) rather than corrupting others."""
    cfg = _cfg(E=2, k=1)
    key = jax.random.PRNGKey(0)
    p = M.moe_init(key, cfg, jnp.float32)
    # bias the router so everything goes to expert 0 (positive inputs ×
    # positive column -> expert 0 wins for every token)
    p["router"]["w"] = jnp.zeros_like(p["router"]["w"]).at[:, 0].set(100.0)
    x = jnp.abs(jax.random.normal(key, (32, cfg.d_model))) + 0.1
    # cap = max(8, ceil(32*1*1.25/2) -> 24): 8 of 32 rows overflow
    y, _ = M._moe_local_math(x, p, cfg)
    y_ref = _dense_oracle(x, p, cfg)
    # the first `capacity` routed tokens match; some tail tokens are zero
    match = np.isclose(np.asarray(y), np.asarray(y_ref),
                       atol=1e-5).all(axis=1)
    zeros = (np.asarray(y) == 0).all(axis=1)
    assert (match | zeros).all()
    assert zeros.sum() > 0


EP_PROG = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig
    from repro.distributed.context import DistContext
    from repro.launch.mesh import make_mesh
    from repro.models import moe as M

    out = {}
    for impl in ("ep_a2a", "ep_token_a2a"):
        cfg = ModelConfig(family="moe", n_layers=1, d_model=16, n_heads=2,
                          n_kv_heads=2, d_ff=32, vocab_size=64, n_experts=8,
                          top_k=2, moe_d_ff=32, moe_impl=impl,
                          moe_capacity=8.0,
                          param_dtype="float32", compute_dtype="float32")
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = DistContext.for_mesh(mesh, fsdp=True)
        key = jax.random.PRNGKey(0)
        p = M.moe_init(key, cfg, jnp.float32)
        x = jax.random.normal(jax.random.fold_in(key, 1),
                              (4, 8, cfg.d_model))
        y_local, _ = M.moe_apply(p, cfg, x, None)
        with mesh:
            y_dist, _ = jax.jit(
                lambda p, x: M.moe_apply(p, cfg, x, ctx))(p, x)
        out[impl] = {"err": float(jnp.max(jnp.abs(y_local - y_dist))),
                     "ep": M.use_ep(cfg, ctx)}
    print(json.dumps(out))
""")


def test_ep_paths_match_local():
    """Both EP schedules (mask+psum baseline and token-routed a2a, §Perf B4)
    must agree with the single-device oracle."""
    out = subprocess.run([sys.executable, "-c", EP_PROG],
                         capture_output=True, text=True, cwd=REPO,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    for impl, r in data.items():
        assert r["ep"] is True, (impl, r)
        assert r["err"] < 2e-4, (impl, r)
