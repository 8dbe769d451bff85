"""In-step fused detection under donation — the step carries its own canary.

PR 3 made the rotating checksum canary donation-safe by splitting the
check/arm pair around the step (``arm_current`` after the step produces a
buffer, ``check`` just before the next step consumes it): 2 launches/step.
This module inverts the control flow — instead of the runtime calling the
digest around the step, the *step function itself* is wrapped so that

  * the digest of canary slice ``s % K`` of the INPUT state (the check),
  * the user step, and
  * the digest of slice ``(s+1) % K`` of the OUTPUT state (the arm)

are one jitted program per rotation ``r = s % K``.  XLA's dataflow
scheduling orders the input-slice digest reads before the donated in-place
writes, so the pre- and post-step state versions CAN meet in one launch —
the thing the host-side pair could never do across a donated dispatch.

Launch/sync/byte contract (DESIGN.md §4.2, "in-step fused" column):

  * 1 combined launch/step (the step's own dispatch; detection adds zero
    extra launches) — down from 2 (donated pair) or from 1 step + 1
    digest launch (non-donated ``check_and_arm``);
  * 1 scalar "any mismatch?" device→host sync/step; the per-leaf bad-mask
    vector stays on device until the fault path resolves attribution
    (``FaultReport.resolve``);
  * ~2/K of the state's bytes digested per step — unchanged;
  * 0 steady-state device allocations on the digest path: the persistent
    packing buffer and the write-generation reference table are donated
    through every call, exactly as in the standalone fused launches.

The price is K rotation-specialised compilations of the step: each
rotation digests a different leaf subset, so each is its own executable.
``FusedStepFactory`` AOT-compiles (``jit(...).lower(...).compile()``) and
caches the K executables globally — keyed by (plan, K, step_fn, donate,
rotation, arg shapes) so campaign-style callers that build one factory
per trial over the same structure never recompile — and warms them
eagerly or lazily per the ``warm`` knob.  After warmup the hot path never
retraces (``kernels.digest.STATS.traces`` stays flat).

Detection semantics are bit-identical to the non-donated
``check_and_arm`` protocol: slice ``s % K`` of the input state is
verified against the generation that armed it (step ``s-1``'s output
digest — the same buffer version), and slice ``(s+1) % K`` of the output
is armed for step ``s+1``'s check.  The digest subcomputation only
*reads* the state on either side of the user step and never feeds back
into it, yet the trajectory is not bit-exact to a separately compiled
step: XLA fuses the step differently inside the larger program, and on
TPU the two round differently.  So the replay rung recomputes through
these same executables (``replay``), never through a bare step.
"""

from __future__ import annotations

import time
import weakref
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.detect import ChecksumCanary, FaultReport
from repro.kernels import digest as kdigest

#: global executable cache — step_fn -> {(plan, K, donate, rotation,
#: args_sig): (compiled, union, chk)}.  The outer map is WEAKLY keyed on
#: the step-fn object: callers that build many factories over one
#: long-lived step function (one per campaign trial — the campaign holds
#: the function) share entries and never recompile, while callers that
#: mint a fresh step function per run (launch/train.py, launch/serve.py)
#: leak nothing — when the run's factory and step function are released,
#: their K executables evaporate with the weak key.
_EXEC_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def clear_executable_cache() -> None:
    """Drop every cached fused-step executable immediately (the weak
    keying already reclaims entries whose step function has died)."""
    _EXEC_CACHE.clear()


def evict_mesh(mesh) -> int:
    """Drop cached fused-step executables keyed on ``mesh`` (via their
    digest/parity plans) across ALL live step functions — the elastic
    remesh path: a dead mesh's executables must release their buffers,
    and a second drill in-process must never hit one."""
    from repro.kernels import digest as kdigest
    mk = kdigest._mesh_key(mesh)
    n = 0
    for by_key in _EXEC_CACHE.values():
        stale = [k for k in by_key if kdigest.key_on_mesh(k, mk)]
        for k in stale:
            del by_key[k]
        n += len(stale)
    return n


def _sds(tree):
    """ShapeDtypeStructs of a pytree — compile without executing.

    NamedShardings ride along: a mesh-sharded state (DESIGN.md §5) must
    AOT-compile against its real layout, or the executable would insert
    reshards around the shard_map'd canary subcomputation."""
    from jax.sharding import NamedSharding

    def sds(x):
        sharding = getattr(x, "sharding", None)
        if isinstance(sharding, NamedSharding):
            return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                        sharding=sharding)
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))

    return jax.tree_util.tree_map(sds, tree)


def _args_signature(args) -> Tuple:
    from jax.sharding import NamedSharding

    def sig(x):
        sh = getattr(x, "sharding", None)
        spec = str(sh.spec) if isinstance(sh, NamedSharding) else None
        return (jnp.shape(x), jnp.result_type(x).name, spec)

    flat, treedef = jax.tree_util.tree_flatten(args)
    return (treedef, tuple(sig(x) for x in flat))


class FusedStepFactory:
    """K rotation-specialised executables of (check ∘ step ∘ arm).

    Built by ``ChecksumCanary.fuse_into_step``.  Drive with::

        new_state, aux, report = factory.step(s, state, *args)

    ``step_fn(state, *args) -> (new_state, aux)`` must take and return the
    canary's plan structure as its first argument/result; ``aux`` (metrics,
    logits, ...) passes through.  ``report`` is ``None`` on the no-fault
    path (after the ONE scalar sync) or a ``FaultReport`` with deferred
    leaf attribution.  On a report the returned ``new_state`` was computed
    FROM the corrupted input and must be discarded by the caller; with
    ``donate=True`` the input state has also been consumed — recovery must
    pivot to snapshot + replay (``RecoveryRuntime(donated=True)``), just
    as with the arm/check pair.

    Compilation accounting: ``n_compiles``/``compile_seconds`` accumulate
    the K-executable warmup cost (the benchmarks report it; each compile
    is one ``compile`` span, which ``compile_seconds`` sums); ``warm()``
    forces the full rotation eagerly and returns the wall time it took.
    """

    def __init__(self, step_fn, canary: ChecksumCanary, *,
                 donate: bool = False, warm: str = "lazy"):
        if warm not in ("lazy", "eager"):
            raise ValueError(f"warm must be 'lazy' or 'eager', got {warm!r}")
        self.step_fn = step_fn
        self.canary = canary
        self.plan = canary.plan
        self.n_slices = canary.n_slices
        self.donate = donate
        self.warm_mode = warm
        self.n_compiles = 0
        self.compile_seconds = 0.0
        self._warmed_sigs: set = set()
        #: the signature of the first-seen step args, memoised so the hot
        #: path never re-flattens the args pytree (a serve-mode factory
        #: would otherwise flatten the full params tree every token).
        #: The factory therefore assumes a STABLE arg structure across
        #: ``step`` calls — a shape change raises an aval mismatch from
        #: the compiled executable rather than silently recompiling.
        self._step_sig = None

    # -- compilation -------------------------------------------------------

    def _build(self, r: int, state_sds, args_sds):
        """Trace + AOT-compile rotation ``r``'s fused executable."""
        from jax.sharding import NamedSharding

        chk = self.canary._slice_indices(r)
        arm = self.canary._slice_indices(r + 1)
        core, union = kdigest.check_arm_subcomputation(self.plan, chk, arm) \
            if (chk or arm) else (None, ())
        plan, step_fn = self.plan, self.step_fn
        pstore = self.canary.parity_store
        pplan = pstore.plan if (pstore is not None and core is not None) \
            else None

        def pin_layout(new_state):
            # mesh loops: constrain the OUTPUT state to the input layout.
            # GSPMD would otherwise pick different shardings for some
            # leaves, which (a) breaks the steady state of an AOT
            # executable (step s+1's input no longer matches the compiled
            # sharding) and (b) defeats donation, which can only reuse a
            # donated buffer into an identically-laid-out output.
            def c(x, s):
                sh = getattr(s, "sharding", None)
                if isinstance(sh, NamedSharding):
                    return jax.lax.with_sharding_constraint(x, sh)
                return x
            return jax.tree_util.tree_map(c, new_state, state_sds)

        if core is None:
            # degenerate rotation (fewer leaves than slices): plain step
            def fused(state, *args):
                new_state, aux = step_fn(state, *args)
                return pin_layout(new_state), aux
            donate_argnums = (0,) if self.donate else ()
            jfn = jax.jit(fused, donate_argnums=donate_argnums)
            lowered = jfn.lower(state_sds, *args_sds)
        elif pplan is None:
            def fused(state, buf, ref_read, ref_write, *args):
                in_leaves = plan.leaves(state)
                new_state, aux = step_fn(state, *args)
                new_state = pin_layout(new_state)
                out_leaves = plan.leaves(new_state)
                # one digest launch spanning both state versions: the
                # check slice reads the INPUT buffers (scheduled before
                # the donated in-place writes), the arm slice reads the
                # step's output
                buf, flag, bad, new_write = core(
                    buf,
                    [in_leaves[i] for i in chk] +
                    [out_leaves[i] for i in arm],
                    ref_read, ref_write)
                return new_state, aux, buf, flag, bad, new_write
            donate_argnums = (1, 3) + ((0,) if self.donate else ())
            jfn = jax.jit(fused, donate_argnums=donate_argnums)
            table_sds = _sds(self.canary.reference)
            buf_sds = _sds(self.plan.take_buffer(union))
            lowered = jfn.lower(state_sds, buf_sds, table_sds, table_sds,
                                *args_sds)
        else:
            def fused(state, buf, ref_read, ref_write, parity, *args):
                in_leaves = plan.leaves(state)
                p_old = pplan.leaves(state)
                new_state, aux = step_fn(state, *args)
                new_state = pin_layout(new_state)
                out_leaves = plan.leaves(new_state)
                buf, flag, bad, new_write = core(
                    buf,
                    [in_leaves[i] for i in chk] +
                    [out_leaves[i] for i in arm],
                    ref_read, ref_write)
                # incremental parity (old ^ new ^ parity) riding the SAME
                # fused launch, gated on this step's own fault flag: XLA
                # schedules the old-shard reads with the check-slice
                # digest reads, before the donated in-place writes
                new_parity = pplan.update_leaves(
                    parity, p_old, pplan.leaves(new_state), flag)
                return new_state, aux, buf, flag, bad, new_write, new_parity
            donate_argnums = (1, 3, 4) + ((0,) if self.donate else ())
            jfn = jax.jit(fused, donate_argnums=donate_argnums)
            table_sds = _sds(self.canary.reference)
            buf_sds = _sds(self.plan.take_buffer(union))
            parity_sds = _sds(pstore.parity)
            lowered = jfn.lower(state_sds, buf_sds, table_sds, table_sds,
                                parity_sds, *args_sds)
        with obs.span("compile") as timed:
            compiled = lowered.compile()
        self.compile_seconds += timed.seconds
        self.n_compiles += 1
        return compiled, union, tuple(chk)

    def _executable(self, r: int, sig, state, args):
        per_fn = _EXEC_CACHE.get(self.step_fn)
        if per_fn is None:
            per_fn = _EXEC_CACHE[self.step_fn] = {}
        pstore = self.canary.parity_store
        key = (self.plan, self.n_slices, self.donate, r, sig,
               pstore.plan if pstore is not None else None)
        ent = per_fn.get(key)
        if ent is None:
            ent = self._build(r, _sds(state), _sds(args))
            per_fn[key] = ent
        return ent

    def warm(self, state, *args) -> float:
        """Compile all K rotation executables for these arg shapes (no
        step compute — AOT lower/compile only).  Returns wall seconds;
        idempotent per arg signature."""
        return self._warm(_args_signature(args), state, args)

    def _warm(self, sig, state, args) -> float:
        if sig in self._warmed_sigs:
            return 0.0
        t0 = time.perf_counter()
        for r in range(self.n_slices):
            self._executable(r, sig, state, args)
        self._warmed_sigs.add(sig)
        return time.perf_counter() - t0

    # -- hot path ----------------------------------------------------------

    def step(self, s: int, state, *args):
        """Run one fused step: returns ``(new_state, aux, report)``.

        ONE launch (the combined step+detection executable) and ONE scalar
        host sync; the write-generation table commit and generation bump
        ride the canary's begin/commit plumbing, so interleaving with
        ``refresh`` (post-recovery) behaves exactly like the pair path.
        """
        new_state, aux, flag, bad, chk, ref_read = self._launch(s, state,
                                                                args)
        if flag is None:
            return new_state, aux, None
        with obs.span("step.flag_sync", step=s):
            faulted = bool(kdigest.fetch(flag))            # ONE host sync
        if not faulted:
            return new_state, aux, None
        can = self.canary
        # the commit already bumped the generation; the rows this check
        # actually compared against live in ref_read — recovery certifies
        # reconstructions against THEM
        can._fault_reference = ref_read
        # under donation the faulting input version was consumed by this
        # very launch: the parity rung's survivors are dead, and the report
        # says so up front (consumed=True) instead of letting the rung
        # discover it post-hoc
        report = FaultReport(
            s, "checksum",
            detail="in-step fused check",
            resolver=lambda: can._attribute(chk, bad),
            consumed=self.donate)
        return new_state, aux, report

    def replay(self, s: int, state, *args):
        """Recompute step ``s`` for the replay rung: ``(new_state, aux)``
        from the very executable ``step(s, ...)`` ran, since only the same
        compiled program reproduces the hot path's bits (a separately
        compiled step rounds differently on TPU).  The check's flag is
        never read: it compares the replayed state against tables armed
        after the fault.  The canary and parity it advances are stale
        until the caller re-digests and rebuilds them once recovery ends
        (``launch/train.py`` does)."""
        new_state, aux, *_ = self._launch(s, state, args)
        return new_state, aux

    def _launch(self, s: int, state, args):
        """Dispatch rotation ``s % K`` and commit the canary generation and
        parity it produced: ``(new_state, aux, flag, bad, chk, ref_read)``,
        with ``flag`` None for a degenerate rotation.  No host sync."""
        # the signature is the dispatch key — memoised on first use so
        # steady-state steps never re-flatten the args pytree
        sig = self._step_sig
        if sig is None:
            sig = self._step_sig = _args_signature(args)
        if self.warm_mode == "eager":
            self._warm(sig, state, args)
        can = self.canary
        r = s % self.n_slices
        compiled, union, chk = self._executable(r, sig, state, args)
        kdigest.STATS.launches += 1
        if not union:                       # degenerate rotation: no digest
            new_state, aux = compiled(state, *args)
            return new_state, aux, None, None, chk, None
        ref_read, ref_write = can.begin_update()
        pstore = can.parity_store
        if pstore is not None:
            new_state, aux, buf, flag, bad, new_write, new_parity = compiled(
                state, self.plan.take_buffer(union), ref_read, ref_write,
                pstore.parity, *args)
            pstore.commit(new_parity, s + 1)
        else:
            new_state, aux, buf, flag, bad, new_write = compiled(
                state, self.plan.take_buffer(union), ref_read, ref_write,
                *args)
        self.plan.put_buffer(union, buf)
        can.commit_update(new_write)
        return new_state, aux, flag, bad, chk, ref_read
