"""RecoveryRuntime — the paper's §3.5 runtime, for the training loop.

The paper's runtime is a SIGSEGV handler: inactive on the hot path, invoked
only on a fault, it looks up the recovery kernel in the Recovery Table,
pulls the kernel's parameters out of the stalled process image and replays
the RSI; recovery is exact-or-abort.

This runtime wraps a training loop the same way: it does *nothing* until a
``FaultReport`` arrives (from a detector or from an external signal such as
a device loss), then walks the leaf's recovery ladder:

    rung 0  triage        FlipTracker-style classification BEFORE any
                          repair: localise the flip from the digest pair
                          and TOLERATE it (re-arm the digests, zero work)
                          when a certificate proves it harmless — dead
                          (never-read) bytes or a below-epsilon mantissa
                          perturbation in an EMA moment
    rung 1  eq1 / opt_iv  induction-state partner recovery (Eq. (1), ns):
                          the ``iv`` counter block AND the optimizer-owned
                          induction leaves (step counter ``t`` affine,
                          bias-correction/decay factors recomputed from
                          the consensus iteration)
    rung 2  shard_patch   restore ONLY the injured shard's addressable
                          bytes from a version-matched, digest-certified
                          micro-snapshot (mesh loops; DESIGN.md §5)
    rung 3  replica_vote  bitwise TMR vote across DP replicas
    rung 4  parity_xor    XOR parity shard reconstruction
    rung 5  replay        pure-step replay from a verified micro-snapshot
    rung 6  checkpoint    classic disk restore (the paper's strawman)

Every rung's repair is digest-verified before the loop resumes; a rung that
cannot certify an exact repair escalates (the abort-instead-of-SDC rule,
§5.3.1).  The runtime records per-recovery telemetry (rung used, wall time,
steps lost) — the data behind the Fig-7/8 benchmarks.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.detect import ChecksumCanary, FaultReport, block_of_leaf
from repro.core.induction import IVRegistry, RecoveryAbort
from repro.core.microcheckpoint import MicroCheckpointer
from repro.core.parity import ParityStore
from repro.core.recovery_table import (
    RUNG_CHECKPOINT,
    RUNG_EQ1,
    RUNG_OPT_IV,
    RUNG_PARITY,
    RUNG_REMESH,
    RUNG_REPLAY,
    RUNG_REPLICA,
    RUNG_SHARD,
    RUNG_TRIAGE,
    RecoveryTable,
)
from repro.core.replay import replay, upload
from repro.kernels import digest as kdigest
from repro.kernels import ops as kops
from repro.optim.optimizers import QBLOCK

#: triage epsilon certificate: a mantissa perturbation of an EMA moment is
#: tolerable when |new - old| <= max(REL_EPS * max(|old|, |new|), ABS_FLOOR)
#: — the induced relative error in the update direction is of the same
#: order, far below the optimizer's own stochastic noise floor.
TRIAGE_REL_EPS = 1e-5
TRIAGE_ABS_FLOOR = 1e-12


@dataclass
class RecoveryEvent:
    """Telemetry for one recovery (one Fig-8 sample)."""
    step: int
    report: FaultReport
    rung: str = ""                 # rung that succeeded
    attempted: List[str] = field(default_factory=list)
    wall_seconds: float = 0.0
    steps_replayed: int = 0
    bytes_moved: int = 0           # host→device bytes (shard_patch rung)
    recovered: bool = False
    phase_seconds: Dict[str, float] = field(default_factory=dict)


class RecoveryFailed(RuntimeError):
    """Every rung exhausted — the job must fall back to cold restart."""


class RecoveryRuntime:
    """Off-hot-path recovery engine for a pure training loop.

    Parameters
    ----------
    step_fn     : jitted step(state, batch) -> (state, metrics)
    replay_step : optional ``(step, state, batch) -> (state, metrics)``
                  that runs step ``step`` through the loop's own hot-path
                  executable (``FusedStepFactory.replay``).  Replay uses
                  it when given: only the same compiled program recomputes
                  the hot path's bits (default: ``step_fn``)
    batch_fn    : pure batch_fn(step) -> batch  (index-addressable pipeline)
    iv_registry : IVRegistry from ``core.icp.promote`` (ICP output)
    micro       : MicroCheckpointer (per-step IV log + K-step snapshots)
    parity      : optional ParityStore (core/parity.py) over the
                  param/opt shards — the device-resident XOR parity the
                  canary maintains in-launch; enables the parity_xor rung
    replicas    : optional callable step -> list of ≥2 healthy replica state
                  trees (pure-DP deployments); used by the TMR rung
    checkpoint  : optional (load_fn() -> (state, step)) — disk restore
    canary      : optional ChecksumCanary over the same state — the parity
                  rung localises finite flips against its reference table
                  (per-shard digests on a mesh, trial reconstruction
                  off-mesh) and digest-certifies every reconstruction
                  before resume
    donated     : the loop runs its step with ``donate_argnums``: on a
                  trap the pre-step state buffers have been consumed by
                  the step and MUST NOT be touched — the ladder pivots
                  unconditionally to the in-HBM micro-snapshot + IV
                  replay rung (then classic checkpoint), and replay does
                  not consult the dead state for sharding
    shardings   : pytree of NamedShardings for the train state (mesh
                  loops) — places replayed snapshots back on the mesh
                  when donation left no live reference, each device
                  receiving only its addressable slice
    triage      : enable rung 0 — classify the injured (leaf, shard)
                  against the canary's reference digest pair BEFORE any
                  repair, and tolerate certified-harmless flips in place
                  (zero bytes moved, zero steps replayed).  Requires a
                  canary; only checksum reports with live buffers are
                  classifiable, everything else falls straight through
    """

    def __init__(self, *, step_fn, batch_fn, iv_registry: IVRegistry,
                 micro: MicroCheckpointer,
                 parity: Optional[ParityStore] = None,
                 replicas: Optional[Callable] = None,
                 checkpoint: Optional[Callable] = None,
                 table: Optional[RecoveryTable] = None,
                 donated: bool = False,
                 shardings=None,
                 canary: Optional[ChecksumCanary] = None,
                 triage: bool = False,
                 elastic: Optional[Callable] = None,
                 replay_step: Optional[Callable] = None):
        self.step_fn = step_fn
        self.replay_step = replay_step
        self.batch_fn = batch_fn
        self.ivs = iv_registry
        self.micro = micro
        self.parity = parity
        self.replicas = replicas
        self.checkpoint = checkpoint
        self.table = table
        self.donated = donated
        self.shardings = shardings
        self.canary = canary
        self.triage = triage
        #: hard-loss handler ``(state, report, step) -> ElasticResume``
        #: (``launch/elastic.ElasticManager.hook`` — core/ stays
        #: layering-clean by taking a callable, not the manager)
        self.elastic = elastic
        #: the remesh rung's side channel: the full resume bundle (new
        #: ctx/step/bfn/canary/parity) for the loop to swap in after
        #: ``recover`` returns the reconstructed state
        self.pending_remesh = None
        self.events: List[RecoveryEvent] = []

    # ------------------------------------------------------------------
    # Rung implementations.  Each returns the repaired state or raises
    # RecoveryAbort; the ladder driver verifies and escalates.
    # ------------------------------------------------------------------

    def _induction_leaf(self, state, name: str):
        """The live leaf a full-path registry key names (``iv/…`` resolves
        into the counter block, anything else through the state tree)."""
        if name.startswith("iv/"):
            return state.get("iv", {}).get(name[3:])
        return _leaf_by_key(state, name)

    def _rung_eq1(self, state, report: FaultReport, step: int):
        """Repair corrupted induction state from healthy partners.

        Registered as BOTH the ``eq1`` and ``opt_iv`` rungs (the Recovery
        Table decides which name a leaf's ladder advertises): one Eq. (1)
        majority diagnosis runs over every affine counter the registry
        knows — the ``iv`` block AND the optimizer-owned step counter —
        then (a) affine outliers are rewritten to their family value at
        the consensus iteration n*, and (b) derived entries (bias
        corrections, Adafactor decay) whose stored bits disagree with the
        recomputation at n* are rewritten in place.  All of it is scalar
        arithmetic: zero snapshot bytes, zero replayed steps.
        """
        vals: Dict[str, int] = {}
        for name in self.ivs.specs:
            leaf = self._induction_leaf(state, name)
            if leaf is not None:
                vals[name] = int(leaf)
        if not vals:
            raise RecoveryAbort("no registered induction leaves in state")
        n_star, bad = self.ivs.diagnose(vals)
        if n_star is None:
            raise RecoveryAbort("no consensus among induction variables")
        derived_bad: List[str] = []
        for name in self.ivs.derived:
            leaf = self._induction_leaf(state, name)
            if leaf is None:
                continue
            have = np.asarray(leaf)
            want = np.asarray(self.ivs.derived_value(name, n_star),
                              have.dtype)
            if have.tobytes() != want.tobytes():   # bit compare, not value
                derived_bad.append(name)
        if not bad and not derived_bad:
            raise RecoveryAbort(
                "induction state consistent — fault is elsewhere")
        out = dict(state)
        new_iv = dict(state["iv"])
        swap: Dict[str, object] = {}
        for name in bad:
            v = self.ivs.specs[name].value_at(n_star)
            if name.startswith("iv/"):
                k = name[3:]
                new_iv[k] = jnp.asarray(v, jnp.asarray(state["iv"][k]).dtype)
            else:
                leaf = self._induction_leaf(state, name)
                swap[name] = jnp.asarray(v, jnp.asarray(leaf).dtype)
        for name in derived_bad:
            leaf = self._induction_leaf(state, name)
            swap[name] = jnp.asarray(self.ivs.derived_value(name, n_star),
                                     jnp.asarray(leaf).dtype)
        out["iv"] = new_iv
        if swap:
            out = jax.tree_util.tree_map_with_path(
                lambda path, leaf: swap.get(kops.leaf_key(path), leaf), out)
        repaired = sorted(bad) + sorted(derived_bad)
        return out, (f"repaired {repaired} via Eq.(1) consensus n={n_star}"
                     + (f" (derived recompute: {sorted(derived_bad)})"
                        if derived_bad else ""))

    # -- rung 0: triage -------------------------------------------------

    def _rung_triage(self, state, report: FaultReport, step: int):
        """Classify the injured (leaf, shard) BEFORE any repair and
        tolerate certified-harmless flips in place (FlipTracker, arXiv:
        1809.01362).  Single-event-upset fault model: the Fletcher digest
        pair the canary already holds is an error-locating code for one
        flipped bit, so triage can name the (bit, word) coordinates and
        the implied pre-flip bits with no second copy of the data.

        Certificates (EVERY injured leaf must certify, else abort):

          * dead region — the flip landed on bytes the update never reads
            (int8-quantised moment pad tail; the absmax scale of an
            all-pad block): bitwise harmless, and the next update rewrites
            them wholesale;
          * below-epsilon moment perturbation — a mantissa-tail flip in a
            float EMA moment whose old/new values differ by at most
            ``TRIAGE_REL_EPS`` relative: the induced update-direction
            error is of the same order and decays geometrically under the
            EMA, far below the optimizer's stochastic noise floor.

        Tolerate = re-arm the digest table rows to the tolerated bits
        (``canary.refresh(keys=…)`` patches BOTH generations without a
        bump) and resume with the state untouched — zero bytes moved,
        zero steps replayed.  Anything uncertifiable (multi-word damage,
        exponent-scale perturbations, non-moment leaves) escalates:
        exact-or-abort is preserved because tolerate never ALTERS state,
        it only re-certifies it.
        """
        if not self.triage:
            raise RecoveryAbort("triage disabled")
        if self.canary is None:
            raise RecoveryAbort("triage needs a canary digest reference")
        if report.detector != "checksum":
            raise RecoveryAbort(
                "only digest-attributed faults are classifiable")
        if getattr(report, "consumed", False):
            raise RecoveryAbort(
                "faulting buffers donated into the step — nothing to "
                "classify in place")
        injured = list(report.leaves or ())
        if not injured:
            raise RecoveryAbort("no leaf attribution to classify")
        notes = []
        for key in injured:
            leaf = _leaf_by_key(state, key)
            if leaf is None:
                raise RecoveryAbort(f"injured leaf {key} not in state")
            notes.append(f"{key}: "
                         f"{self._certify_tolerable(state, key, leaf)}")
        # tolerate MUST re-arm: the digest rows still describe the
        # pre-flip bits, so without this every later check would re-fire
        # on a value we have decided to live with (partial refresh — both
        # generations patched, no bump, unrelated rows untouched)
        self.canary.refresh(state, keys=injured)
        return state, "tolerated without repair — " + "; ".join(notes)

    def _certify_tolerable(self, state, key: str, leaf) -> str:
        """Certificate check for one injured leaf; returns the tolerance
        note or raises RecoveryAbort."""
        host = np.asarray(leaf)
        bit, cands = self._localise_flip(key, leaf, host)
        if all(self._dead_element(state, key, j) for j, _, _ in cands):
            return (f"dead-region flip (bit {bit}, "
                    f"{len(cands)} candidate word(s), never read)")
        if not self._moment_leaf(key):
            raise RecoveryAbort(
                f"{key} is not an EMA moment — no tolerance certificate")
        worst = 0.0
        for j, cur_w, old_w in cands:
            if self._dead_element(state, key, j):
                continue
            new_v = _word_value(host.dtype, cur_w)
            old_v = _word_value(host.dtype, old_w)
            if not (np.isfinite(new_v) and np.isfinite(old_v)):
                raise RecoveryAbort(
                    f"{key}: non-finite endpoint at word {j} — escalate")
            delta = abs(new_v - old_v)
            tol = max(TRIAGE_REL_EPS * max(abs(new_v), abs(old_v)),
                      TRIAGE_ABS_FLOOR)
            if delta > tol:
                raise RecoveryAbort(
                    f"{key}: |Δ|={delta:.3e} at word {j} exceeds the "
                    f"epsilon certificate ({tol:.3e}) — escalate")
            worst = max(worst, delta)
        return (f"sub-epsilon moment perturbation (bit {bit}, "
                f"|Δ|≤{worst:.3e})")

    def _localise_flip(self, key: str, leaf, host: np.ndarray):
        """(bit, [(flat_element, cur_word, old_word), …]) for the single
        flip the digest-pair evidence implies, or RecoveryAbort when the
        evidence is inconsistent with any single-bit flip.  ``to_i32``
        packs one word per element for every supported dtype, so word
        index == flat element index (shard-local indices are translated
        to leaf-flat coordinates on a mesh)."""
        ref = np.asarray(self.canary.fault_reference_digest(key))
        if ref.ndim == 2:                       # sharded canary rows
            cur_rows = kdigest.host_shard_checksums(leaf)
            idxs = kdigest.shard_indices(leaf)
            seen, mismatch = set(), []
            for d, idx in enumerate(idxs):
                sig = tuple((sl.start, sl.stop) for sl in idx)
                if sig in seen:                 # replicated slice
                    continue
                seen.add(sig)
                if not np.array_equal(cur_rows[d], ref[d]):
                    mismatch.append((d, idx))
            if not mismatch:
                raise RecoveryAbort(
                    f"{key}: shard digests match the reference — stale "
                    f"attribution")
            if len(mismatch) > 1:
                raise RecoveryAbort(
                    f"{key}: {len(mismatch)} shards mismatch — more than "
                    f"one event, escalate")
            d, idx = mismatch[0]
            sub = np.ascontiguousarray(host[idx])
            words = kdigest._host_i32(sub).view(np.uint32)
            sol = kdigest.locate_single_flip(ref[d], cur_rows[d],
                                             words.size)
            if sol is None:
                raise RecoveryAbort(
                    f"{key} shard {d}: digest deltas inconsistent with a "
                    f"single-bit flip — escalate")
            bit, delta, local = sol
            starts = [0 if sl.start is None else int(sl.start)
                      for sl in idx]
            out = []
            for j in local:
                multi = np.unravel_index(j, sub.shape) if sub.shape else ()
                g = tuple(int(a) + s for a, s in zip(multi, starts))
                gflat = int(np.ravel_multi_index(g, host.shape)) \
                    if host.shape else 0
                cur_w = int(words[j])
                out.append((gflat, cur_w, (cur_w - delta) & 0xFFFFFFFF))
            return bit, out
        words = kdigest._host_i32(host).view(np.uint32)
        cur = kdigest.host_checksum(host)
        if np.array_equal(cur, ref):
            raise RecoveryAbort(
                f"{key}: digest matches the reference — stale attribution")
        sol = kdigest.locate_single_flip(ref, cur, words.size)
        if sol is None:
            raise RecoveryAbort(
                f"{key}: digest deltas inconsistent with a single-bit "
                f"flip — escalate")
        bit, delta, cand = sol
        return bit, [(j, int(words[j]),
                      (int(words[j]) - delta) & 0xFFFFFFFF) for j in cand]

    @staticmethod
    def _moment_leaf(key: str) -> bool:
        """Float EMA-moment leaves — the only state the epsilon
        certificate applies to (params/IVs always escalate)."""
        return key.startswith(("opt/m/", "opt/v/", "opt/stats/")) \
            and not key.endswith("/q")

    def _dead_element(self, state, key: str, j: int) -> bool:
        """Is flat element ``j`` of ``key`` dead — bytes the optimizer
        update never reads and rewrites wholesale each step?  True for
        the int8-quantised moment pad tail (``_q8`` pads to QBLOCK;
        ``_dq8`` slices the logical size back out) and for the absmax
        scale of an all-pad block."""
        base = None
        for pre in ("opt/m/", "opt/v/"):
            if key.startswith(pre):
                base = key[len(pre):]
                break
        if base is None:
            return False
        if base.endswith("/q"):
            p = _leaf_by_key(state, "params/" + base[:-len("/q")])
            return p is not None and j >= int(np.prod(jnp.shape(p)))
        if base.endswith("/scale"):
            p = _leaf_by_key(state, "params/" + base[:-len("/scale")])
            return p is not None and \
                j * QBLOCK >= int(np.prod(jnp.shape(p)))
        return False

    def _rung_replica(self, state, report: FaultReport, step: int):
        """Bitwise TMR vote across DP replicas of the corrupted leaves."""
        if self.replicas is None:
            raise RecoveryAbort("no replicas maintained")
        reps = self.replicas(step)
        if reps is None or len(reps) < 2:
            raise RecoveryAbort("fewer than 2 healthy replicas")
        bad = set(report.leaves)

        def heal(path, leaf, *partner_leaves):
            key = kops.leaf_key(path)
            if bad and key not in bad:
                return leaf
            if len(partner_leaves) >= 2:
                return kops.vote3(leaf, partner_leaves[0], partner_leaves[1])
            return partner_leaves[0]  # 2-way: trust the healthy replica

        out = jax.tree_util.tree_map_with_path(heal, state, *reps[:2])
        return out, f"replica vote over {len(reps)} replicas"

    def _rung_parity(self, state, report: FaultReport, step: int):
        """Reconstruct the injured (leaf, shard) from XOR parity — the
        snapshot-free rung: 0 host-snapshot bytes read, 0 steps replayed,
        O(leaf_bytes/D) reconstructed.

        Covers the FULL state tree (params AND optimizer state — the seed
        repaired only ``state["params"]``, so an opt/EMA-leaf fault
        returned "success" with nothing repaired and burned a verify
        round).  Applicability gates (abort → escalate, never guess):

          * a parity store must be maintained and the faulting version's
            buffers must be LIVE — an in-step fused report under donation
            says ``consumed=True`` and aborts up front (the donated PAIR
            protocol checks before the step consumes, so its reports keep
            live survivors even under donation);
          * at least one injured leaf must be parity-covered (up-front
            RecoveryAbort otherwise — int64/float64 leaves and the IV
            block are not covered);
          * exactly ONE shard per injured leaf: single parity tolerates a
            single lost component per leaf (arXiv:1309.0212), two injured
            shards of one leaf escalate;
          * checksum/external reports are digest-certified against the
            canary's reference table before resume (``host_shard_checksums``
            per shard on a mesh, whole-leaf ``host_checksum`` off-mesh);
            an uncertifiable reconstruction aborts (exact-or-abort).
        """
        store = self.parity
        if store is None:
            raise RecoveryAbort("no parity maintained")
        if getattr(report, "consumed", False):
            raise RecoveryAbort(
                "faulting version donated into the detecting step — "
                "survivors are dead, replay instead")
        injured = list(report.shards or ()) or list(report.leaves or ())
        if not injured:
            # free traps carry no leaf attribution — name suspects via the
            # non-finite scan (the only evidence class a trap leaves)
            injured = _default_verify(state)
        covered = [k for k in injured if store.covers(k)]
        if not covered:
            raise RecoveryAbort("no injured leaf is parity-covered")
        # the table generation the fired check compared against — NOT the
        # current read table, which the fused protocols have already
        # advanced past by the time the fault path runs
        refs = self.canary.fault_reference_digests() \
            if self.canary is not None else None
        certifiable = report.detector in ("checksum", "external")
        on_mesh = store.plan.mesh is not None
        moved = [0, 0]                      # bytes reconstructed, shards
        repaired: Dict[str, object] = {}
        for key in covered:
            leaf = _leaf_by_key(state, key)
            if leaf is None:
                raise RecoveryAbort(f"injured leaf {key} not in state")
            shards = self._locate_shards(leaf, key, report, refs)
            if not shards:
                raise RecoveryAbort(
                    f"cannot localise the injured shard of {key}")
            if len(shards) > 1:
                raise RecoveryAbort(
                    f"{len(shards)} injured shards of {key} — a single "
                    f"parity shard reconstructs exactly one")
            d = shards[0]
            if on_mesh:
                # surviving devices keep their exact buffers; the
                # reconstructed block's bytes move to EVERY device holding
                # that logical block (all replicas — O(leaf_bytes/D) each)
                block = np.asarray(store.reconstruct_shard(leaf, key, d))
                sharding = leaf.sharding
                devs = kdigest.mesh_device_order(sharding.mesh)
                by_dev = {sh.device: sh.data
                          for sh in leaf.addressable_shards}
                holders = set(store.plan.block_devices(key, d))
                bufs = [jax.device_put(block, dev) if i in holders
                        else by_dev[dev] for i, dev in enumerate(devs)]
                new_leaf = jax.make_array_from_single_device_arrays(
                    leaf.shape, sharding, bufs)
                moved[0] += block.nbytes * len(holders)
            else:
                new_leaf = store.reconstruct_leaf(leaf, key, d)
                moved[0] += 4 * store.plan.block_sizes[key][d]
            moved[1] += 1
            if certifiable and refs is not None and key in refs:
                got = kdigest.host_shard_checksums(new_leaf) if on_mesh \
                    else kdigest.host_checksum(np.asarray(new_leaf))
                if not np.array_equal(np.asarray(got),
                                      np.asarray(refs[key])):
                    raise RecoveryAbort(
                        f"reconstruction of {key} shard {d} failed digest "
                        f"certification — escalating")
            repaired[key] = new_leaf

        def swap(path, leaf):
            return repaired.get(kops.leaf_key(path), leaf)

        out = jax.tree_util.tree_map_with_path(swap, state)
        self._last_patched_bytes = moved[0]
        return out, (f"parity reconstruction of {moved[1]} shard(s) of "
                     f"{len(covered)} leaf/leaves ({moved[0]} B, "
                     f"no snapshot, no replay)")

    def _locate_shards(self, leaf, key: str, report: FaultReport,
                       refs) -> List[int]:
        """Which unique logical block(s) of ``leaf`` are injured, in the
        parity plan's block coordinates.  Device-coordinate evidence (the
        sharded canary attributes per DEVICE) is translated through
        ``plan.device_block`` — replicas of one corrupted logical slice
        collapse to ONE injured block, which single parity CAN repair.

        In order of evidence quality:
          1. the report's own (leaf, shard) attribution (sharded canary);
          2. per-shard uint32 digests of the live leaf against the
             canary's reference rows (``host_shard_checksums`` — the
             finite-bitflip case the seed's non-finite-only scan aborted
             on);
          3. off-mesh, where the reference is one whole-leaf digest:
             trial reconstruction — reconstruct each candidate shard in
             turn and keep the one whose repaired leaf matches the
             reference (localisation, repair and certification in one
             O(D · leaf_bytes/D) sweep).  ALL candidates are tried and a
             unique match is required: a false candidate mirrors the XOR
             delta into its own block at the same block-local offset, and
             for a flip of bit b the two complementary word deltas sit
             exactly ``block_len`` apart — Fletcher's weighted term
             shifts by ``2^b * block_len``, which is ``0 mod 2^32``
             whenever ``b + log2(block_len) >= 32``, so high-bit flips
             can digest-collide.  Two matches are indistinguishable by
             parity too (both repairs are parity-consistent), so the
             only exact-or-abort answer is to escalate to replay;
          4. last resort (no canary): per-block non-finite scan.
        """
        store = self.parity
        dmap = store.plan.device_block[key]
        ids = (report.shards or {}).get(key)
        if ids:
            return sorted({dmap[int(i)] for i in ids})
        ref = refs.get(key) if refs else None
        if ref is not None and store.plan.mesh is not None \
                and np.ndim(ref) == 2:
            got = kdigest.host_shard_checksums(leaf)
            bad = np.nonzero(np.any(got != np.asarray(ref), axis=-1))[0]
            if len(bad):
                return sorted({dmap[int(i)] for i in bad})
        if ref is not None and store.plan.mesh is None:
            matches = [
                d for d in range(store.plan.n_blocks[key])
                if np.array_equal(
                    kdigest.host_checksum(np.asarray(
                        store.reconstruct_leaf(leaf, key, d))),
                    np.asarray(ref))]
            if len(matches) == 1:
                return matches
            if len(matches) > 1:
                raise RecoveryAbort(
                    f"{len(matches)} candidate shards of {key} digest-"
                    f"certify (Fletcher collision of the XOR-mirrored "
                    f"repair) — ambiguous, escalating")
        if jnp.issubdtype(jnp.result_type(leaf), jnp.floating):
            if store.plan.mesh is None:
                flat = jnp.asarray(leaf).reshape(-1)
                c = store.plan.block_len[key]
                flat = jnp.pad(flat, (0, store.n_shards * c - flat.shape[0]))
                bad = np.asarray(jnp.any(
                    ~jnp.isfinite(flat.reshape(store.n_shards, c)), axis=1))
            else:
                uniq, _ = store.plan.slices[key]
                bad = np.asarray([
                    bool(jnp.any(~jnp.isfinite(
                        leaf[tuple(slice(a, b) for a, b in idx)])))
                    for idx in uniq])
            idx = np.nonzero(bad)[0]
            if len(idx):
                return [int(i) for i in idx]
        return []

    def _rung_shard_patch(self, state, report: FaultReport, step: int):
        """Restore ONLY the injured shards' addressable bytes (mesh loops).

        Applicability gates (abort → escalate, never guess):
          * the report must carry (leaf, shard) attribution — only the
            sharded canary produces it;
          * the loop must not have donated the state (the live healthy
            shards are the other half of the patch);
          * the newest snapshot must be VERSION-MATCHED (``snap.step ==
            step``): the canary certifies the live buffer against the
            digests of the same state version, so only a same-version
            snapshot can supply bit-exact replacement bytes — an older
            one would silently mix state versions (the SDC the paper's
            exact-or-abort rule exists to prevent);
          * the injured (leaf, shard) units must digest-certify in the
            snapshot (``MicroCheckpointer.verify_shards``).

        The patch rebuilds each corrupt leaf with
        ``jax.make_array_from_single_device_arrays``: healthy devices
        keep their existing shard buffers (zero copies), only the injured
        shards' bytes cross host→device.  Byte movement is reported — the
        point of the rung is that it is ~state_bytes/n_shards, not
        state_bytes."""
        shards = dict(getattr(report, "shards", None) or {})
        if not shards:
            raise RecoveryAbort("no (leaf, shard) attribution")
        if self.donated:
            raise RecoveryAbort("donated buffers are dead — replay instead")
        if all(k.startswith("iv/") for k in shards):
            raise RecoveryAbort("IV block repairs via Eq.(1)")
        snap = self.micro.latest(before=step)
        if snap is None:
            raise RecoveryAbort("no snapshot available")
        if snap.step != step:
            raise RecoveryAbort(
                f"no version-matched snapshot (have step {snap.step}, "
                f"fault is against version {step})")
        rotten = self.micro.verify_shards(snap, shards)
        if rotten:
            raise RecoveryAbort(f"snapshot shards failed verification: "
                                f"{rotten[:3]}")
        host = {kops.leaf_key(p): leaf for p, leaf in
                jax.tree_util.tree_flatten_with_path(snap.state)[0]}
        moved = [0, 0]                      # bytes, shard units

        def heal(path, leaf):
            key = kops.leaf_key(path)
            ids = set(shards.get(key) or ())
            if not ids:
                return leaf
            sharding = leaf.sharding
            devs = kdigest.mesh_device_order(sharding.mesh)
            idxs = snap.shard_slices[key]
            by_dev = {sh.device: sh.data for sh in leaf.addressable_shards}
            bufs = []
            for d, dev in enumerate(devs):
                if d in ids:
                    piece = np.ascontiguousarray(host[key][idxs[d]])
                    bufs.append(jax.device_put(piece, dev))
                    moved[0] += piece.nbytes
                    moved[1] += 1
                else:
                    bufs.append(by_dev[dev])
            return jax.make_array_from_single_device_arrays(
                leaf.shape, sharding, bufs)

        out = jax.tree_util.tree_map_with_path(heal, state)
        self._last_patched_bytes = moved[0]
        return out, (f"patched {moved[1]} shard(s) of {len(shards)} "
                     f"leaf/leaves ({moved[0]} B moved) from snapshot "
                     f"@{snap.step}")

    def _step_at(self, s: int, state, batch):
        if self.replay_step is not None:
            return self.replay_step(s, state, batch)
        return self.step_fn(state, batch)

    def _rung_replay(self, state, report: FaultReport, step: int):
        """Replay from the newest snapshot ≤ step, uploaded first and then
        digest-verified as uploaded (exact-or-abort)."""
        snap = self.micro.latest(before=step)
        if snap is None:
            raise RecoveryAbort("no snapshot available")
        start = self._upload(snap.state, state)
        rotten = self.micro.verify(snap, start)
        if rotten:
            raise RecoveryAbort(f"snapshot failed verification: {rotten[:3]}")
        res = replay(self._step_at, self.batch_fn, start, snap.step, step)
        self._last_replayed = res.steps_replayed
        return res.state, f"replayed {res.steps_replayed} steps from {snap.step}"

    def _rung_checkpoint(self, state, report: FaultReport, step: int):
        """Classic restore — the baseline the paper seeks to avoid."""
        if self.checkpoint is None:
            raise RecoveryAbort("no checkpoint loader configured")
        ck_state, ck_step = self.checkpoint()
        res = replay(self._step_at, self.batch_fn,
                     self._upload(ck_state, state), ck_step, step)
        self._last_replayed = res.steps_replayed
        return res.state, f"restored step {ck_step} + replayed to {step}"

    def _upload(self, host_state, state):
        """A host state on the device, placed like the live ``state`` (or
        by ``shardings`` when donation left no live buffers)."""
        return upload(host_state, like_state=None if self.donated else state,
                      shardings=self.shardings)

    def _rung_remesh(self, state, report: FaultReport, step: int):
        """HARD loss: devices are gone, not corrupt — shrink the mesh and
        keep training (DESIGN.md §7).  Delegates to the attached elastic
        handler (survivor-honest gather + certify, parity reconstruction
        of the dead rows' shards, old-mesh cache eviction, one re-lower
        on the degraded context) and swaps the runtime's own executables
        so any later rung/replay this event — and every subsequent one —
        runs against the new mesh.  The full resume bundle is left on
        ``pending_remesh`` for the training loop."""
        if self.elastic is None:
            raise RecoveryAbort("no elastic handler attached")
        rows = tuple(getattr(report, "lost_rows", ()) or ())
        if not rows:
            raise RecoveryAbort("report names no lost rows")
        resume = self.elastic(state, report, step)
        self.pending_remesh = resume
        self.step_fn = resume.step
        self.replay_step = None     # the old mesh's executables are gone
        self.batch_fn = resume.bfn
        self.shardings = resume.shardings
        if resume.canary is not None:
            self.canary = resume.canary
        if resume.pstore is not None:
            self.parity = resume.pstore
        ev = resume.event
        self._last_patched_bytes = ev.bytes_reconstructed
        return resume.state, (
            f"remeshed dp {ev.old_dp}->{ev.new_dp} (rows {ev.lost_rows} "
            f"lost), {ev.blocks_reconstructed} blocks "
            f"({ev.bytes_reconstructed} B) parity-reconstructed, "
            f"{ev.certified_blocks} survivor blocks certified, "
            f"re-lowered once in {ev.relower_seconds:.2f}s")

    _RUNGS = {
        RUNG_TRIAGE: _rung_triage,
        RUNG_EQ1: _rung_eq1,
        RUNG_OPT_IV: _rung_eq1,     # same consensus engine, opt-IV ladder
        RUNG_SHARD: _rung_shard_patch,
        RUNG_REPLICA: _rung_replica,
        RUNG_PARITY: _rung_parity,
        RUNG_REPLAY: _rung_replay,
        RUNG_REMESH: _rung_remesh,
        RUNG_CHECKPOINT: _rung_checkpoint,
    }

    # ------------------------------------------------------------------
    # Ladder driver
    # ------------------------------------------------------------------

    def recover(self, state, report: FaultReport, step: int,
                verify: Optional[Callable] = None,
                ladder: Optional[Sequence[str]] = None):
        """Walk the ladder; return (repaired_state, RecoveryEvent).

        ``verify(state) -> List[str]`` names still-corrupt leaves (empty =
        verified).  Default: non-finite scan over float leaves.
        """
        # in-step fused detection defers leaf attribution: the hot path
        # fetched only the scalar mismatch flag, so the per-leaf bad-mask
        # vector is still on device — materialise it now (fault path; one
        # extra transfer) so the Recovery Table lookup and the targeted
        # rungs see the corrupted leaf paths exactly as with the pair
        # protocol.
        ev = RecoveryEvent(step=step, report=report)
        # ``wall_seconds`` is the ``recover`` span, ``phase_seconds`` the
        # ``recover.<rung>`` span of each attempted rung (repair + verify)
        with obs.span("recover", step=step) as whole:
            report.resolve()
            ladder = list(ladder) if ladder is not None \
                else self._ladder(report)
            cand = self._walk(ladder, state, report, step,
                              verify or _default_verify, ev)
        ev.wall_seconds = whole.seconds
        self.events.append(ev)
        if not ev.recovered:
            raise RecoveryFailed(str(report))
        return cand, ev

    def _walk(self, ladder, state, report: FaultReport, step: int,
              verify: Callable, ev: RecoveryEvent):
        """Try each rung in turn; the first certified repair, or None."""
        for rung in ladder:
            fn = self._RUNGS.get(rung)
            if fn is None:
                continue
            ev.attempted.append(rung)
            self._last_replayed = 0
            self._last_patched_bytes = 0
            bad = None
            with obs.span("recover." + rung) as timed:
                try:
                    cand, detail = fn(self, state, report, step)
                except RecoveryAbort as e:
                    cand, detail = None, str(e)
                else:
                    bad = verify(cand)
                timed.attrs["steps"] = self._last_replayed
            ev.phase_seconds[rung] = timed.seconds
            if cand is None:
                ev.report.detail += f" | {rung}: {detail}"
                continue
            if bad:
                # exact-or-abort: the repair did not certify — escalate
                ev.report.detail += f" | {rung}: post-verify failed {bad[:2]}"
                continue
            ev.rung = rung
            ev.recovered = True
            ev.steps_replayed = self._last_replayed
            ev.bytes_moved = self._last_patched_bytes
            ev.report.detail += f" | {rung}: {detail}"
            return cand
        return None

    def _ladder(self, report: FaultReport) -> List[str]:
        """Choose the ladder from the Recovery Table (or the default)."""
        if getattr(report, "lost_rows", None):
            # HARD loss: the devices themselves are gone — no in-place
            # rung applies (there is nothing to patch into), no replay
            # helps (snapshots are sharded onto the dead mesh).  Remesh
            # onto the survivors; only the classic checkpoint restore
            # sits below it.
            return [RUNG_REMESH, RUNG_CHECKPOINT]
        if self.donated:
            # the pre-step state was donated into the step — there are no
            # live buffers for the in-place rungs (Eq.(1), TMR, parity,
            # shard patch) to read or repair: pivot straight to snapshot +
            # IV replay.  ONE exception: the donated-PAIR protocol checks
            # the buffer BEFORE the step consumes it, so a checksum report
            # with ``consumed=False`` still has live survivors — the
            # parity rung can reconstruct the injured shard in place with
            # no snapshot and no replay (in-step fused reports under
            # donation say ``consumed=True`` and skip it).
            ladder = [RUNG_REPLAY, RUNG_CHECKPOINT]
            if (self.parity is not None
                    and report.detector in ("checksum", "external")
                    and not getattr(report, "consumed", False)):
                ladder.insert(0, RUNG_PARITY)
            if self._triage_applies(report):
                # the donated-PAIR protocol checks before the step
                # consumes, so its reports still have live bytes to
                # classify — triage rides ahead of parity/replay
                ladder.insert(0, RUNG_TRIAGE)
            return ladder
        if self.table is not None and report.leaves:
            entry = self.table.lookup(report.leaves[0])
            if entry is not None:
                return list(entry.ladder)
        if report.leaves and all(k.startswith("iv/") for k in report.leaves):
            return [RUNG_EQ1, RUNG_REPLAY, RUNG_CHECKPOINT]
        if report.leaves and all(
                k in self.ivs.specs or k in self.ivs.derived
                for k in report.leaves):
            # optimizer-owned induction leaves (opt/t, bias corrections):
            # the opt-IV branch of the same Eq. (1) consensus engine
            return [RUNG_OPT_IV, RUNG_REPLAY, RUNG_CHECKPOINT]
        ladder = [RUNG_EQ1, RUNG_REPLICA, RUNG_PARITY, RUNG_REPLAY,
                  RUNG_CHECKPOINT]
        if getattr(report, "shards", None):
            # mesh attribution: try the byte-minimal shard patch first —
            # its gates (version match, shard certification) abort cleanly
            # into the generic ladder when it does not apply
            ladder.insert(0, RUNG_SHARD)
        if self._triage_applies(report):
            ladder.insert(0, RUNG_TRIAGE)
        return ladder

    def _triage_applies(self, report: FaultReport) -> bool:
        """Rung 0 gate: enabled, a canary to certify against, digest
        attribution, and live (un-donated) buffers to classify."""
        return (self.triage and self.canary is not None
                and report.detector == "checksum"
                and not getattr(report, "consumed", False)
                and bool(report.leaves))

    # -- telemetry -------------------------------------------------------

    def summary(self) -> Dict:
        n = len(self.events)
        rec = [e for e in self.events if e.recovered]
        by_rung: Dict[str, int] = {}
        for e in rec:
            by_rung[e.rung] = by_rung.get(e.rung, 0) + 1
        return {
            "events": n,
            "recovered": len(rec),
            "recovery_rate": len(rec) / n if n else 1.0,
            "by_rung": by_rung,
            "mean_wall_ms": 1e3 * float(np.mean([e.wall_seconds
                                                 for e in rec])) if rec else 0.0,
            "mean_steps_replayed": float(np.mean([e.steps_replayed
                                                  for e in rec])) if rec else 0.0,
        }


# ---------------------------------------------------------------------------
# Serving recovery policy — slot-scoped eviction vs whole-state ladder
# ---------------------------------------------------------------------------
#
# The training runtime above walks a per-leaf ladder because every rung can
# repair state IN PLACE.  The serving engine has a cheaper primitive the
# trainer lacks: each batch slot's decode state is rebuildable from its
# request's token log (prefix replay — the serving RSI), and the slot-view
# canary attributes a fault to (leaf, slot).  The policy below decides, per
# FaultReport, between
#
#   * ``slots`` — evict ONLY the injured slots to prefix replay; healthy
#     slots keep decoding the very next engine step.  Requires slot
#     attribution (checksum units or per-slot non-finite flags) and bounds
#     the suspect-token window:
#
#       - checksum: the in-step fused canary checks each row against the
#         digest armed ONE step earlier (the generation tables alternate
#         every step), so a mismatch proves the corruption arose in the
#         single inter-step gap just crossed.  The only corrupt-derived
#         token is the detection step's own output, which the engine
#         discards for evicted slots — zero ACCEPTED tokens are suspect,
#         retract = 0.  (This is also what makes the storm livelock-free:
#         a fault costs eviction + replay, never accepted progress.)
#       - nonfinite: the free trap fires only when the poison reaches the
#         logits, which for recurrent/SSM-style caches can lag the flip by
#         several steps.  Retract the last K-1 accepted tokens — the
#         at-rest window the rotating canary leaves unchecked between a
#         unit's check and its next arm — as the conservative bound.
#
#     tests/test_serving.py pins the bit-exactness of both paths (replay
#     determinism regenerates retracted-but-clean tokens identically).
#   * ``engine`` — no slot attribution (e.g. an external signal): evict
#     every active slot — the serving analogue of the trainer's
#     whole-state replay rung.  Without a canary bound on detection
#     latency the retraction must be the full log (replay from prompt).


@dataclass
class ServingRecoveryPlan:
    """What the engine must do about one FaultReport."""
    scope: str                     # 'slots' | 'engine'
    slots: List[int]               # slots to evict (scope='slots')
    retract: Optional[int] = None  # suspect tokens to rescind; None = all
    reason: str = ""


def plan_serving_recovery(report: FaultReport, *, n_slices: int,
                          nonfinite_slots: Sequence[int] = ()
                          ) -> ServingRecoveryPlan:
    """Slot-scoped eviction vs whole-state eviction for a serving fault.

    ``n_slices``       : the canary's K (0 = no canary: free traps only).
    ``nonfinite_slots``: active slots whose logits went non-finite this
                         step (the engine's free trap — computed in-launch
                         and fetched with the token payload).
    """
    slots = set(report.injured_slots()) if report is not None else set()
    slots.update(nonfinite_slots)
    checksum = report is not None and report.detector == "checksum"
    if checksum:
        # one-step detection latency (checked row == row armed last step):
        # no accepted token predates the corruption — nothing to rescind
        retract = 0
    else:
        # nonfinite trap: poison may have sat in the unchecked at-rest
        # window for up to K-1 steps before reaching the logits
        retract = max(0, n_slices - 1) if n_slices else None
    if slots:
        return ServingRecoveryPlan(
            scope="slots", slots=sorted(slots), retract=retract,
            reason=f"slot attribution ({report.detector if report else 'nonfinite'})")
    if report is not None:
        leaves = report.resolve()
        if leaves and all(block_of_leaf(k) is not None for k in leaves):
            # Paged pool: every corrupted leaf is a pool block with no
            # owning slot — the flip landed on free (or scratch) bytes
            # that no live sequence reads.  Nothing to evict; the engine
            # just re-certifies the injured blocks' digests.
            return ServingRecoveryPlan(
                scope="slots", slots=[], retract=0,
                reason="checksum attribution to unowned pool blocks — "
                       "no live victim")
    return ServingRecoveryPlan(
        scope="engine", slots=[], retract=None,
        reason="no slot attribution — evict all active slots")


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _word_value(dtype, word: int) -> float:
    """Decode a packed ``to_i32`` word back to the float it encodes (the
    triage epsilon certificate compares old/new VALUES, not bits)."""
    dt = np.dtype(dtype)
    if dt.itemsize == 4:
        return float(np.array([word & 0xFFFFFFFF],
                              np.uint32).view(np.float32)[0])
    if dt.itemsize == 2:
        return float(np.array([word & 0xFFFF], np.uint16).view(dt)[0])
    raise RecoveryAbort(f"no value decoding for dtype {dt}")


def _leaf_by_key(tree, key: str):
    found = [None]

    def visit(path, leaf):
        if kops.leaf_key(path) == key:
            found[0] = leaf
        return leaf

    jax.tree_util.tree_map_with_path(visit, tree)
    return found[0]


_VERIFY_CACHE: Dict[object, Callable] = {}


def _default_verify(state) -> List[str]:
    """Non-finite scan over float leaves — names corrupt leaves.

    Fused like the digest engine (DESIGN.md §4.2): one jitted device pass
    producing a per-leaf flag vector and ONE host transfer, instead of a
    blocking ``isfinite().all()`` fetch per leaf.  The compiled scan is
    cached per state structure, so repeated rung verifications never
    retrace."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(state)
    keys = [kops.leaf_key(p) for p, _ in flat]
    float_idx = [i for i, (_, x) in enumerate(flat)
                 if jnp.issubdtype(jnp.result_type(x), jnp.floating)]
    if not float_idx:
        return []
    sig = (treedef, tuple((jnp.shape(x), jnp.result_type(x).name)
                          for _, x in flat))
    fn = _VERIFY_CACHE.get(sig)
    if fn is None:
        fn = jax.jit(lambda leaves: jnp.stack(
            [~jnp.isfinite(leaf).all() for leaf in leaves]))
        _VERIFY_CACHE[sig] = fn
    mask = kdigest.fetch(fn([flat[i][1] for i in float_idx]))
    return sorted(keys[i] for i, b in zip(float_idx, mask) if b)
