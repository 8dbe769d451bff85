"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see 1 CPU device;
only the dry-run process forces 512 placeholder devices (see launch/dryrun).
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.data.pipeline import TokenPipeline
from repro.train.loop import make_train_state, make_train_step

@pytest.fixture(scope="session")
def tiny_cfg():
    """A very small config for fast loop-level tests."""
    cfg = get_config("iterpro-100m").smoke()
    return cfg


@pytest.fixture(scope="session")
def tiny_setup(tiny_cfg):
    """(cfg, state0, jitted step_fn, batch_fn) shared across tests."""
    B, S = 2, 32
    pipe = TokenPipeline(tiny_cfg.model.vocab_size, S, B, seed=0)
    state = make_train_state(tiny_cfg, jax.random.PRNGKey(0), global_batch=B)
    step = jax.jit(make_train_step(tiny_cfg, global_batch=B))
    bfn = lambda s: pipe.batch_at(s)
    # warm the jit cache once for the whole session
    st, m = step(state, bfn(0))
    jax.block_until_ready(m["loss"])
    return tiny_cfg, state, step, bfn
