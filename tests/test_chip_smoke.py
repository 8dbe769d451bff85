"""``chip_smoke.py`` rehearsed on the CPU at smoke size.

The phases are the script's own functions, driven here with the platform
check skipped (the test calls them directly) and with Pallas kernels in
interpret mode.  The script itself must refuse a CPU: that is checked
through ``main`` as well.  The four-chip drill runs in a child process
with four forced CPU devices.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402


@pytest.fixture(scope="module")
def smoke_cfg():
    return cs.full_config().smoke()


def test_main_refuses_a_cpu_and_prints_no_result(capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_kernel_audit_flags_interpreted_kernels():
    from repro.kernels import ops
    with cs.kernel_audit() as audit:
        jax.block_until_ready(ops.checksum(jnp.arange(1234, dtype=jnp.int32)))
    assert audit["interpreted"] and not audit["kernels"]
    with pytest.raises(cs.CheckFailed, match="interpret mode"):
        cs.check_kernels(audit, {"_row_checksum_kernel"}, "cpu")


def test_train_phase_smoke(smoke_cfg):
    figures = cs.train_phase(smoke_cfg, batch=2, seq=32, steps=8,
                             inject_every=3)
    assert figures["faults"] == 2
    assert sum(figures["by_rung"].values()) == 2
    assert figures["leaves_digested"] > 0


def test_serve_phase_smoke(smoke_cfg):
    figures = cs.serve_phase(smoke_cfg, n_requests=4, prompt_len=16, gen=8,
                             slots=2, inject_every=5)
    assert figures["faults"]["injected"] > 0
    assert figures["healthy_bit_identical"] > 0


ELASTIC_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    sys.path.insert(0, os.getcwd())
    import chip_smoke as cs

    cfg = cs.full_config(fsdp=True).smoke()
    print(json.dumps(cs.elastic_phase(
        cfg, mesh=cs.MESH4, batch=4, seq=16, steps=cs.DRILL_STEPS,
        inject_every=cs.DRILL_INJECT, kill_at=cs.DRILL_KILL)))
""")


def test_elastic_phase_smoke_on_four_cpu_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", ELASTIC_PROG],
                         capture_output=True, text=True, cwd=REPO, env=env,
                         timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["resumed_on"] == {"data": 1, "model": 2}
    assert out["by_rung"]["remesh"] == 1
    assert out["blocks_reconstructed"] > 0
