"""Pallas TPU kernels for the IterPro detection/redundancy hot path.

checksum — row-granular Fletcher digest (the ~free canary detector)
digest   — fused single-launch whole-state digesting (DigestPlan: one
           pallas_call + one host sync per canary check, DESIGN.md §4.2)
vote     — bitwise TMR majority across replicas (replica repair)
parity   — XOR parity fold / reconstruction (manufactured redundancy)

Each kernel: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling),
with jit'd wrappers in ops.py and pure-jnp oracles in ref.py.  All
algorithms are bitwise/integer — tests assert bit-exact equality.
Kernels run compiled on TPU and in the Pallas interpreter elsewhere;
``backend.interpret_mode`` is the one switch.
"""

from repro.kernels import digest, ops, ref  # noqa: F401
from repro.kernels.digest import DigestPlan, plan_for  # noqa: F401
