"""Continuous-batching serving engine with slot-isolated recovery.

The training loop's resilience story (rotating checksum canary, one fused
launch + one scalar sync per step, exact replay from a tiny log) transfers
to serving as follows (DESIGN.md §6):

* **Slot-major decode state.**  The engine owns S batch *slots*.  Every
  decode-cache leaf is laid out ``[slot, ...]`` over per-slot B=1 caches
  (including the per-slot position counter, so requests at different
  depths coexist), and one vmapped decode executable advances all S lanes
  per engine step.  Admission and eviction are ``dynamic_update_slice``
  writes into the slot axis through ONE compiled function with a traced
  slot index — never a retrace, never a reshape of live state.

* **Per-slot canary slices.**  The rotating checksum canary is built over
  the *slot view* (``core.detect.slot_view``): digest units are (leaf,
  slot) pairs, so a checksum fault names its injured slot(s) directly.
  The check of the input view's slice ``s % K`` and the arm of the output
  view's slice ``(s+1) % K`` ride the decode's own launch (the
  ``check_arm_subcomputation`` core embedded in the engine's jitted step,
  exactly as core/fused_step.py does for training), donated or not.

* **Hot-path contract** (hard-asserted by benchmarks/serving_slo.py):
  one logical launch per engine step (vmapped decode + forced-token
  select + in-step canary + per-slot finite trap, one executable per
  rotation) + one scalar fault sync (``kernels.digest.fetch`` of the
  any-mismatch flag).  The accepted tokens come back in the same
  launch's payload — the serving data plane, not a detection cost.

* **Slot-isolated recovery.**  On a fault the policy
  (``core.recover.plan_serving_recovery``) evicts ONLY the injured slots:
  each victim's last ``K-1`` accepted tokens are rescinded (the provable
  suspect window under a K-slice canary), the request re-enters the queue
  front, and its slot's canary rows are re-certified against the lane's
  current bytes so no unit double-fires.  Healthy slots keep decoding the
  very next engine step — they even keep the fault step's own tokens,
  which are valid because lanes are computationally independent.
  Re-admission is prefix replay, the serving RSI: B=1 prefill + forced
  decode over the token log rebuilds a bit-identical lane (pinned by
  tests/test_serving.py).

* **Admission keeps the canary sound** with a partial ``refresh`` of the
  admitted slot's rows (patched in BOTH generations, generation counter
  untouched — the core/detect.py partial-refresh contract), so units of
  other slots armed before the admission still verify.

* **Paged KV pool** (default where supported; ``serving/paged.py``).
  Instead of one dense ``[max_len]`` cache per slot, every cache leaf is
  a shared block pool ``[n_blocks, block_size, ...]`` plus per-slot block
  tables: a request owns ``ceil((P + 1 + max_new) / block_size)`` blocks,
  admission is a block-budget decision, and freed blocks return to the
  pool on completion/eviction.  The hot path stays ONE launch: a Pallas
  block-gather kernel (``kernels/paged_kv.py``) materialises each slot's
  owned blocks, the *unmodified* vmapped decode runs on the gathered view
  (bit-exact vs the dense engine by construction), and the written row
  scatters back — all inside the same jitted step as the canary.  Canary
  units become (leaf, block) + per-slot ``pos``; block → owning slot is a
  host allocator lookup, so a flip on a FREE block evicts nobody.  All
  data movement is fixed-shape (scratch block 0 absorbs masked lanes), so
  block alloc/free churn causes 0 retraces.

* **Chunked prefill** (``prefill_chunk=C`` > 0, paged mode): long prompts
  prefill in C-token chunks interleaved one per engine-run iteration with
  decode steps, so a long prompt no longer stalls the S decode lanes —
  bounding short-request p99 under mixed traffic (measured by
  ``benchmarks/serving_slo.py``).  Chunk outputs are token-equivalent to
  monolithic prefill (same values, different fp reduction order;
  deterministic per platform, pinned by tests/test_serving.py).

Mesh mode (``ctx=DistContext``): params shard per ``launch/specs``; the
slot-major cache (or block pool) is replicated and the canary goes
shard-local over the replicated view (PR-5 machinery), keeping the
1-launch/1-sync contract with an all-reduced fault flag.  Slot-sharded
caches are a ROADMAP item.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.detect import (ChecksumCanary, FaultReport, block_leaf_prefix,
                               block_of_leaf, slot_leaf_prefix, slot_view)
from repro.core.faults import flip_bit
from repro.core.fused_step import _args_signature, _sds
from repro.core.recover import plan_serving_recovery
from repro.kernels import digest as kdigest
from repro.kernels.ops import leaf_key
from repro.models.registry import get_model
from repro.serving import paged as pgd
from repro.serving.paged import AdmissionError, BlockAllocator, PoolSaturated
from repro.serving.request import Request, RequestQueue

#: global fused-engine-step executable cache — keyed by (plan, K, donate,
#: S, model cfg, rotation, arg signature) so every engine over the same
#: smoke/serve configuration (one per test, one per benchmark run) shares
#: the K rotation-specialised executables and never recompiles.
_EXEC_CACHE: Dict[Tuple, Tuple] = {}

#: module-level prefill / admit executables, keyed by (model cfg, max_len,
#: [slots,] replication sharding) — engines over the same serving shape
#: (baseline vs storm run of a benchmark, one engine per test) share them,
#: so only the first engine's first admission pays compilation.
_PREFILL_CACHE: Dict[Tuple, object] = {}
_ADMIT_CACHE: Dict[Tuple, object] = {}

#: paged-mode admission-path executables (zero-on-alloc, span scatter,
#: chunk prefill, lane activate/deactivate) — keyed by pool geometry so
#: every engine over the same serving shape shares them.
_PAGED_FN_CACHE: Dict[Tuple, Dict] = {}


def evict_mesh(mesh) -> int:
    """Drop every serving-side executable keyed on ``mesh`` (cache keys
    carry the replication NamedSharding and/or a sharded digest plan) —
    the elastic remesh path's stale-executable guard."""
    from repro.kernels import digest as kdigest
    mk = kdigest._mesh_key(mesh)
    n = 0
    for cache in (_EXEC_CACHE, _PREFILL_CACHE, _ADMIT_CACHE,
                  _PAGED_FN_CACHE):
        stale = [k for k in cache if kdigest.key_on_mesh(k, mk)]
        for k in stale:
            del cache[k]
        n += len(stale)
    return n

_BIT_WIDTH = {"float32": 32, "int32": 32, "uint32": 32,
              "bfloat16": 16, "float16": 16, "int16": 16,
              "int8": 8, "uint8": 8}


def _pcts(xs: Sequence[float]) -> Dict[str, float]:
    if not xs:
        return {"mean": 0.0, "p50": 0.0, "p99": 0.0}
    a = np.asarray(xs, np.float64)
    return {"mean": float(a.mean()),
            "p50": float(np.percentile(a, 50)),
            "p99": float(np.percentile(a, 99))}


@dataclass
class ServingReport:
    """Engine telemetry — the data behind the serving SLO benchmark."""
    n_slots: int = 0
    requests: int = 0
    completed: int = 0
    dropped: int = 0
    tokens_out: int = 0
    engine_steps: int = 0
    admissions: int = 0
    admission_rejected: int = 0     # over-budget requests (typed error)
    faults_injected: int = 0
    faults_detected: int = 0
    faults_recovered: int = 0
    faults_on_free_slots: int = 0   # occupant already gone: SDC-risk count
    replay_tokens: int = 0
    retracted_tokens: int = 0
    decode_ms: List[float] = field(default_factory=list)
    #: per-fault recovery wall time: eviction -> victim re-admitted
    recovery_ms: List[float] = field(default_factory=list)
    injured_rids: Set[int] = field(default_factory=set)
    per_request: Dict[int, Dict] = field(default_factory=dict)

    def summary(self) -> Dict:
        d, r = _pcts(self.decode_ms), _pcts(self.recovery_ms)
        return {
            "requests": self.requests,
            "completed": self.completed,
            "dropped": self.dropped,
            "tokens_out": self.tokens_out,
            "engine_steps": self.engine_steps,
            "admissions": self.admissions,
            "admission_rejected": self.admission_rejected,
            "slots": self.n_slots,
            "faults": {"injected": self.faults_injected,
                       "detected": self.faults_detected,
                       "recovered": self.faults_recovered,
                       "on_free_slots": self.faults_on_free_slots},
            "mean_decode_ms": d["mean"],
            "p50_decode_ms": d["p50"],
            "p99_decode_ms": d["p99"],
            "mean_recovery_ms": r["mean"],
            "p50_recovery_ms": r["p50"],
            "p99_recovery_ms": r["p99"],
            "replay_tokens": self.replay_tokens,
            "retracted_tokens": self.retracted_tokens,
        }


class ServingEngine:
    """Iteration-level scheduler + slot-major decoder + slot canary.

    Parameters
    ----------
    cfg           : full config (``cfg.model`` drives the model family)
    n_slots       : batch slots S (concurrent requests per engine step)
    max_len       : decode-cache capacity (prompt + generation budget)
    canary_slices : rotating canary K over the S×L (leaf, slot) units;
                    0 disables the canary (free traps only)
    donate        : donate the slot-major cache into the engine step —
                    the production in-place KV-update setting
    ctx           : DistContext for mesh serving (params sharded, cache
                    replicated, shard-local canary) or None
    seed          : params init seed
    max_replays   : fault-evictions a request survives before it is
                    dropped (bounds livelock under a persistent-fault
                    adversary)
    paged         : None = auto (paged KV pool where the family supports
                    it — linear caches, 1-D rope); False forces the dense
                    per-slot cache; True errors if unsupported
    block_size    : KV-pool block size in token positions (paged mode;
                    ``max_len`` rounds up to a multiple)
    prefill_chunk : 0 = monolithic prefill; C > 0 prefills prompts in
                    C-token chunks interleaved with decode steps (paged
                    mode only)
    pool_blocks   : total pool blocks incl. the scratch block (0 = full
                    capacity: every slot can hold a max-size request)
    """

    def __init__(self, cfg, *, n_slots: int = 4, max_len: int = 64,
                 canary_slices: int = 4, donate: bool = True,
                 ctx=None, seed: int = 0, max_replays: int = 8,
                 verbose: bool = False, paged: Optional[bool] = None,
                 block_size: int = 8, prefill_chunk: int = 0,
                 pool_blocks: int = 0, parity: bool = False):
        self.cfg = cfg
        self.m = cfg.model
        self.model = get_model(self.m)
        self.S = int(n_slots)
        self.max_len = int(max_len)
        self.K = int(canary_slices)
        self.donate = bool(donate)
        self.ctx = ctx if (ctx is not None and ctx.enabled) else None
        self.max_replays = int(max_replays)
        self.verbose = verbose
        self.block_size = int(block_size)
        self.prefill_chunk = int(prefill_chunk)

        params = self.model.init(self.m, jax.random.PRNGKey(seed))
        self._repl = None
        if self.ctx is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.launch.specs import param_shardings
            psh, _ = param_shardings(self.ctx, cfg, params)
            params = jax.device_put(params, psh)
            self._repl = NamedSharding(self.ctx.mesh, PartitionSpec())
        self.params = params

        # at-rest parity over the STATIC params (core/parity.py): serving
        # never mutates them, so one build at load time + healthy digests
        # recorded here let `scrub_params` detect and repair silent
        # at-rest corruption in O(bytes/D) with no weight reload
        self.parity_store = None
        self._param_refs: Optional[Dict[str, np.ndarray]] = None
        if parity:
            from repro.core.parity import ParityStore
            self.parity_store = ParityStore(params, ctx=self.ctx)
            self.parity_store.build(params)
            plan = self.parity_store.plan
            on_mesh = plan.mesh is not None
            self._param_refs = {
                k: (np.asarray(kdigest.host_shard_checksums(leaf))
                    if on_mesh
                    else np.asarray(kdigest.host_checksum(np.asarray(leaf))))
                for k, leaf in zip(plan.keys, plan.leaves(params))}

        # paged-mode resolution: auto-detect unless forced off
        self.paged = False
        if paged is not False:
            ml = -(-self.max_len // self.block_size) * self.block_size
            probe = self.model.make_decode_cache(self.m, 1, ml)
            supported = pgd.paged_supported(self.model, self.m, probe, ml)
            if paged and not supported:
                raise ValueError(
                    "paged=True: this family/config has no paged-KV "
                    "support (needs linear non-windowed caches, 1-D rope "
                    "and a prefill_chunk entry point)")
            self.paged = supported
            if self.paged:
                self.max_len = ml

        tok = jnp.zeros((self.S,), jnp.int32)
        if self.paged:
            # shared block pool + per-slot block tables; block 0 scratch
            self.max_blocks = self.max_len // self.block_size
            self.n_blocks = int(pool_blocks) or (1 + self.S * self.max_blocks)
            if self.n_blocks < 2:
                raise ValueError("pool_blocks must be >= 2")
            per_slot = self.model.make_decode_cache(self.m, 1, self.max_len)
            pool = pgd.make_block_pool(per_slot, self.n_blocks,
                                       self.block_size)
            bt = jnp.zeros((self.S, self.max_blocks), jnp.int32)
            pos = jnp.zeros((self.S,), jnp.int32)
            amask = jnp.zeros((self.S,), bool)
            if self._repl is not None:
                pool = jax.device_put(
                    pool, jax.tree_util.tree_map(lambda _: self._repl, pool))
                bt, pos, amask, tok = (jax.device_put(x, self._repl)
                                       for x in (bt, pos, amask, tok))
            self.pool, self.bt, self.pos, self.amask = pool, bt, pos, amask
            self.cache = None
            self._bt_np = np.zeros((self.S, self.max_blocks), np.int32)
            self.alloc = BlockAllocator(self.n_blocks)
        else:
            # slot-major decode state: per-slot B=1 caches stacked on a
            # leading [slot] axis (positions become a (S,) vector —
            # per-slot depths for free); tok holds each lane's next input
            per_slot = self.model.make_decode_cache(self.m, 1, self.max_len)
            cache = jax.tree_util.tree_map(
                lambda l: jnp.stack([l] * self.S), per_slot)
            if self._repl is not None:
                cache = jax.device_put(
                    cache,
                    jax.tree_util.tree_map(lambda _: self._repl, cache))
                tok = jax.device_put(tok, self._repl)
            self.cache = cache
        self.tok = tok

        self.canary: Optional[ChecksumCanary] = None
        self.plan = None
        self._slot_keys: List[Tuple[str, ...]] = []
        self._block_keys: List[Tuple[str, ...]] = []
        self._pos_keys: List[str] = []
        if self.K:
            view = (self._view() if self.paged
                    else slot_view(self.cache, self.S))
            self.canary = ChecksumCanary(view, n_slices=self.K, ctx=self.ctx)
            self.plan = self.canary.plan
            if self.paged:
                self._block_keys = [
                    tuple(k for k in self.plan.keys
                          if k.startswith(block_leaf_prefix(b) + "/"))
                    for b in range(self.n_blocks)]
                self._pos_keys = [f"{slot_leaf_prefix(u)}/pos"
                                  for u in range(self.S)]
            else:
                self._slot_keys = [
                    tuple(k for k in self.plan.keys
                          if k.startswith(slot_leaf_prefix(u) + "/"))
                    for u in range(self.S)]

        model, m, repl, max_len = self.model, self.m, self._repl, self.max_len
        pkey = (m, max_len, repl)
        self._prefill = _PREFILL_CACHE.get(pkey)
        if self._prefill is None:
            self._prefill = jax.jit(
                lambda p, b: model.prefill(p, m, b, None, max_len=max_len))
            _PREFILL_CACHE[pkey] = self._prefill

        akey = (m, max_len, self.S, repl)
        self._admit_exec = None if self.paged else _ADMIT_CACHE.get(akey)
        if self._admit_exec is None and not self.paged:
            def admit_fn(cache, tok, sub, t0, u):
                # slice write with a TRACED slot index: one executable
                # serves every slot — admission/eviction never retraces
                def put(big, small):
                    return jax.lax.dynamic_update_slice(
                        big, small[None].astype(big.dtype),
                        (u,) + (0,) * (big.ndim - 1))
                ncache = jax.tree_util.tree_map(put, cache, sub)
                if repl is not None:
                    ncache = jax.tree_util.tree_map(
                        lambda x: jax.lax.with_sharding_constraint(x, repl),
                        ncache)
                ntok = jax.lax.dynamic_update_slice(tok, t0[None], (u,))
                return ncache, ntok
            self._admit_exec = jax.jit(admit_fn, donate_argnums=(0, 1))
            _ADMIT_CACHE[akey] = self._admit_exec

        # no-forcing device constants (steady state never pays an extra
        # host->device transfer for the forced-token mask)
        fm0 = jnp.zeros((self.S,), bool)
        ft0 = jnp.zeros((self.S,), jnp.int32)
        if self._repl is not None:
            fm0 = jax.device_put(fm0, self._repl)
            ft0 = jax.device_put(ft0, self._repl)
        self._fmask0, self._ftok0 = fm0, ft0

        # host-side slot table
        self.slot_rid: List[Optional[int]] = [None] * self.S
        self._by_slot: Dict[int, Request] = {}
        self._prefilling: Dict[int, Dict] = {}   # paged: slot -> {rq, off}
        self._slot_history: List[Optional[int]] = [None] * self.S
        self.step_count = 0
        self.report = ServingReport(n_slots=self.S)
        self._execs: Dict[int, Tuple] = {}
        self._sig = None
        self._fns = self._paged_fns() if self.paged else None

    # -- paged-mode plumbing ----------------------------------------------

    def _view(self):
        """Canary view of the paged state: (leaf, block) + per-slot pos."""
        return pgd.paged_canary_view(self.pool, self.pos, self.n_blocks,
                                     self.S)

    def _dev(self, x):
        return x if self._repl is None else jax.device_put(x, self._repl)

    def _paged_fns(self) -> Dict:
        """Admission-path executables (module-cached per pool geometry):
        fixed-shape pool writes with traced indices — block churn never
        retraces."""
        key = (self.m, self.S, self.max_blocks, self.block_size,
               self.n_blocks, self._repl)
        fns = _PAGED_FN_CACHE.get(key)
        if fns is not None:
            return fns
        model, m, bs, repl = self.model, self.m, self.block_size, self._repl
        cap = self.max_len

        def pin(tree):
            if repl is None:
                return tree
            return jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(x, repl), tree)

        def zero_fn(pool, bids):
            return pin(pgd.zero_blocks(pool, bids))

        def span_fn(pool, new_kv, bt_row, start, valid):
            return pin(pgd.scatter_span(pool, new_kv, bt_row, start, valid,
                                        bs))

        def chunk_fn(params, pool, bt_row, tokens, pos0, valid):
            ctx_cache = pgd.ctx_from_pool(pool, bt_row, bs, pos0)
            kpos = pgd.ctx_kpos(pos0, cap)
            logits, new_kv = model.prefill_chunk(
                params, m, {"tokens": tokens}, ctx_cache, kpos, pos0, valid,
                None)
            npool = pgd.scatter_span(pool, new_kv["groups"], bt_row, pos0,
                                     valid, bs)
            return pin(npool), logits

        def act_fn(pos, tok, amask, p0, t0, u):
            npos = jax.lax.dynamic_update_slice(pos, p0[None], (u,))
            ntok = jax.lax.dynamic_update_slice(tok, t0[None], (u,))
            nam = jax.lax.dynamic_update_slice(
                amask, jnp.ones((1,), bool), (u,))
            return pin(npos), pin(ntok), pin(nam)

        def deact_fn(amask, u):
            return pin(jax.lax.dynamic_update_slice(
                amask, jnp.zeros((1,), bool), (u,)))

        fns = {
            "zero": jax.jit(zero_fn, donate_argnums=(0,)),
            "span": jax.jit(span_fn, donate_argnums=(0,)),
            "chunk": jax.jit(chunk_fn, donate_argnums=(1,)),
            "activate": jax.jit(act_fn, donate_argnums=(0, 1, 2)),
            "deact": jax.jit(deact_fn, donate_argnums=(0,)),
        }
        _PAGED_FN_CACHE[key] = fns
        return fns

    def _refresh_blocks(self, blocks) -> None:
        """Re-certify the given pool blocks' canary rows after an
        out-of-step pool write (both generations, no generation bump).
        One refresh per block keeps the digest-subset key set bounded —
        every subset is pre-warmed by ``warm()``, so churn never
        retraces."""
        if self.canary is None or not blocks:
            return
        view = self._view()
        for b in sorted(blocks):
            self.canary.refresh(view, keys=self._block_keys[b])

    # -- compiled engine step ---------------------------------------------

    def _build_exec(self, r: int):
        """AOT-compile rotation ``r``'s fused engine step."""
        model, m, S, repl = self.model, self.m, self.S, self._repl
        plan, canary = self.plan, self.canary

        def vdecode(params, cache, tok):
            # per-slot B=1 decode vmapped over the slot axis: every lane
            # advances at ITS OWN position; lanes are computationally
            # independent (the slot-isolation guarantee)
            def one(c, t):
                lg, nc = model.decode_step(params, m, c, t[None], None)
                return lg[0], nc
            return jax.vmap(one)(cache, tok)

        def pin(tree):
            if repl is None:
                return tree
            return jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(x, repl), tree)

        chk = canary._slice_indices(r) if canary else []
        arm = canary._slice_indices(r + 1) if canary else []
        if not (chk or arm):
            # no canary (or degenerate rotation): plain fused step
            def fused(cache, tok, fmask, ftok, params):
                logits, ncache = vdecode(params, cache, tok)
                ncache = pin(ncache)
                nxt = jnp.where(fmask, ftok,
                                jnp.argmax(logits, -1).astype(jnp.int32))
                finite = jnp.isfinite(logits).all(axis=-1)
                payload = jnp.stack([nxt, finite.astype(jnp.int32)], axis=1)
                return ncache, nxt, payload
            jfn = jax.jit(fused,
                          donate_argnums=(0, 1) if self.donate else ())
            lowered = jfn.lower(_sds(self.cache), _sds(self.tok),
                                _sds(self._fmask0), _sds(self._ftok0),
                                _sds(self.params))
            return lowered.compile(), (), ()

        core, union = kdigest.check_arm_subcomputation(plan, chk, arm)

        def fused(cache, tok, fmask, ftok, buf, ref_read, ref_write, params):
            # ONE launch: slot-view slices are free static gathers; the
            # check slice reads the INPUT lanes (scheduled before the
            # donated in-place writes), the arm slice reads the output
            in_leaves = plan.leaves(slot_view(cache, S))
            logits, ncache = vdecode(params, cache, tok)
            ncache = pin(ncache)
            out_leaves = plan.leaves(slot_view(ncache, S))
            nxt = jnp.where(fmask, ftok,
                            jnp.argmax(logits, -1).astype(jnp.int32))
            finite = jnp.isfinite(logits).all(axis=-1)   # per-slot free trap
            buf, flag, bad, new_write = core(
                buf,
                [in_leaves[i] for i in chk] + [out_leaves[i] for i in arm],
                ref_read, ref_write)
            payload = jnp.stack([nxt, finite.astype(jnp.int32)], axis=1)
            return ncache, nxt, payload, flag, bad, buf, new_write

        donate_argnums = (4, 6) + ((0, 1) if self.donate else ())
        jfn = jax.jit(fused, donate_argnums=donate_argnums)
        table_sds = _sds(canary.reference)
        buf_sds = _sds(plan.take_buffer(union))
        lowered = jfn.lower(_sds(self.cache), _sds(self.tok),
                            _sds(self._fmask0), _sds(self._ftok0),
                            buf_sds, table_sds, table_sds, _sds(self.params))
        return lowered.compile(), union, tuple(chk)

    def _build_exec_paged(self, r: int):
        """AOT-compile rotation ``r``'s fused PAGED engine step: Pallas
        block gather -> unmodified vmapped dense decode on the gathered
        view -> fixed-shape token scatter-back, with the canary's
        check/arm riding the same launch over the (leaf, block) + pos
        view.  Bit-exact vs the dense engine by construction (the decode
        computation is literally identical)."""
        model, m, S, repl = self.model, self.m, self.S, self._repl
        plan, canary = self.plan, self.canary
        NB, bs = self.n_blocks, self.block_size

        def vdecode(params, gcache, tok):
            def one(c, t):
                lg, nc = model.decode_step(params, m, c, t[None], None)
                return lg[0], nc
            return jax.vmap(one)(gcache, tok)

        def pin(tree):
            if repl is None:
                return tree
            return jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(x, repl), tree)

        def step_core(params, pool, bt, pos, amask, tok, fmask, ftok):
            gcache = pgd.gathered_cache(pool, bt, pos)
            logits, ngc = vdecode(params, gcache, tok)
            npool = pgd.scatter_token(pool, ngc["groups"], bt, pos, amask,
                                      bs)
            npos = jnp.where(amask, pos + 1, pos)
            nxt = jnp.where(fmask, ftok,
                            jnp.argmax(logits, -1).astype(jnp.int32))
            finite = jnp.isfinite(logits).all(axis=-1)
            return npool, npos, nxt, finite

        chk = canary._slice_indices(r) if canary else []
        arm = canary._slice_indices(r + 1) if canary else []
        if not (chk or arm):
            def fused(pool, bt, pos, amask, tok, fmask, ftok, params):
                npool, npos, nxt, finite = step_core(
                    params, pool, bt, pos, amask, tok, fmask, ftok)
                npool, npos = pin(npool), pin(npos)
                payload = jnp.stack([nxt, finite.astype(jnp.int32)], axis=1)
                return npool, npos, nxt, payload
            jfn = jax.jit(fused,
                          donate_argnums=(0, 2, 4) if self.donate else ())
            lowered = jfn.lower(_sds(self.pool), _sds(self.bt),
                                _sds(self.pos), _sds(self.amask),
                                _sds(self.tok), _sds(self._fmask0),
                                _sds(self._ftok0), _sds(self.params))
            return lowered.compile(), (), ()

        core, union = kdigest.check_arm_subcomputation(plan, chk, arm)

        def fused(pool, bt, pos, amask, tok, fmask, ftok, buf, ref_read,
                  ref_write, params):
            in_leaves = plan.leaves(
                pgd.paged_canary_view(pool, pos, NB, S))
            npool, npos, nxt, finite = step_core(
                params, pool, bt, pos, amask, tok, fmask, ftok)
            npool, npos = pin(npool), pin(npos)
            out_leaves = plan.leaves(
                pgd.paged_canary_view(npool, npos, NB, S))
            buf, flag, bad, new_write = core(
                buf,
                [in_leaves[i] for i in chk] + [out_leaves[i] for i in arm],
                ref_read, ref_write)
            payload = jnp.stack([nxt, finite.astype(jnp.int32)], axis=1)
            return npool, npos, nxt, payload, flag, bad, buf, new_write

        donate_argnums = (7, 9) + ((0, 2, 4) if self.donate else ())
        jfn = jax.jit(fused, donate_argnums=donate_argnums)
        table_sds = _sds(canary.reference)
        buf_sds = _sds(plan.take_buffer(union))
        lowered = jfn.lower(_sds(self.pool), _sds(self.bt), _sds(self.pos),
                            _sds(self.amask), _sds(self.tok),
                            _sds(self._fmask0), _sds(self._ftok0),
                            buf_sds, table_sds, table_sds, _sds(self.params))
        return lowered.compile(), union, tuple(chk)

    def _exec(self, r: int):
        ent = self._execs.get(r)
        if ent is None:
            if self._sig is None:
                arrs = ((self.pool, self.bt, self.pos, self.amask, self.tok,
                         self.params) if self.paged
                        else (self.cache, self.tok, self.params))
                self._sig = ("paged" if self.paged else "dense",
                             _args_signature(arrs))
            key = (self.plan, self.K, self.donate, self.S, self.m, r,
                   self._sig)
            ent = _EXEC_CACHE.get(key)
            if ent is None:
                ent = (self._build_exec_paged(r) if self.paged
                       else self._build_exec(r))
                _EXEC_CACHE[key] = ent
            self._execs[r] = ent
        return ent

    def warm(self) -> float:
        """AOT-compile every rotation executable (idempotent; returns wall
        seconds).  First use per configuration pays; the global cache
        makes later engines free.  Paged engines also pre-warm every
        per-block / per-slot digest-refresh subset, so block alloc/free
        churn at steady state never traces a new digest function."""
        t0 = time.perf_counter()
        for r in range(max(1, self.K)):
            self._exec(r)
        if self.paged and self.canary is not None:
            view = self._view()
            for b in range(self.n_blocks):
                self.canary.refresh(view, keys=self._block_keys[b])
            for u in range(self.S):
                self.canary.refresh(view, keys=[self._pos_keys[u]])
        return time.perf_counter() - t0

    # -- hot path ----------------------------------------------------------

    def _forced_arrays(self):
        forced = [(u, rq.forced[0]) for u, rq in self._by_slot.items()
                  if rq.forced]
        if not forced:
            return self._fmask0, self._ftok0
        fm = np.zeros((self.S,), bool)
        ft = np.zeros((self.S,), np.int32)
        for u, t in forced:
            fm[u] = True
            ft[u] = t
        if self._repl is not None:
            return (jax.device_put(fm, self._repl),
                    jax.device_put(ft, self._repl))
        return jnp.asarray(fm), jnp.asarray(ft)

    def engine_step(self) -> Tuple[np.ndarray, np.ndarray,
                                   Optional[FaultReport]]:
        """Advance every lane one token: ONE logical launch + ONE scalar
        fault sync (+ the token payload transfer — the data plane).

        Returns ``(tokens (S,), finite (S,) bool, report|None)``.  On a
        report the injured lanes' output is corrupt-derived; healthy
        lanes' tokens are valid (lane independence) and are kept.
        """
        s = self.step_count
        fmask, ftok = self._forced_arrays()
        r = s % self.K if self.K else 0
        compiled, union, chk = self._exec(r)
        kdigest.STATS.launches += 1
        report = None
        if self.paged:
            if union:
                can = self.canary
                ref_read, ref_write = can.begin_update()
                (npool, npos, ntok, payload, flag, bad, buf,
                 new_write) = compiled(
                    self.pool, self.bt, self.pos, self.amask, self.tok,
                    fmask, ftok, self.plan.take_buffer(union), ref_read,
                    ref_write, self.params)
                self.plan.put_buffer(union, buf)
                can.commit_update(new_write)
                if bool(kdigest.fetch(flag)):  # the step's ONE fault sync
                    report = FaultReport(
                        s, "checksum", detail="paged block canary",
                        resolver=self._paged_resolver(chk, bad))
            else:
                npool, npos, ntok, payload = compiled(
                    self.pool, self.bt, self.pos, self.amask, self.tok,
                    fmask, ftok, self.params)
            self.pool, self.pos, self.tok = npool, npos, ntok
        elif union:
            can = self.canary
            ref_read, ref_write = can.begin_update()
            (ncache, ntok, payload, flag, bad, buf, new_write) = compiled(
                self.cache, self.tok, fmask, ftok,
                self.plan.take_buffer(union), ref_read, ref_write,
                self.params)
            self.plan.put_buffer(union, buf)
            can.commit_update(new_write)
            if bool(kdigest.fetch(flag)):     # the step's ONE fault sync
                report = FaultReport(
                    s, "checksum", detail="slot canary",
                    resolver=lambda: can._attribute(chk, bad))
            self.cache, self.tok = ncache, ntok
        else:
            ncache, ntok, payload = compiled(
                self.cache, self.tok, fmask, ftok, self.params)
            self.cache, self.tok = ncache, ntok
        self.step_count += 1
        pl = np.asarray(payload)              # data plane: the tokens
        return pl[:, 0], pl[:, 1].astype(bool), report

    def _paged_resolver(self, chk, bad):
        """Attribution closure for a paged-canary fault: translate the
        plan's (leaf, block) keys into ``slotNNN/...`` keys for blocks a
        request owned AT DETECTION TIME (the owner map is snapshotted
        here, before recovery frees anything), so
        ``FaultReport.injured_slots()`` works unchanged.  Flips on
        unowned blocks keep their ``blockNNNN/`` keys — nobody to evict.
        """
        can = self.canary
        owner = dict(self.alloc.owner)

        def resolve():
            leaves, shards = can._attribute(chk, bad)
            def xlat(k):
                b = block_of_leaf(k)
                o = owner.get(b) if b is not None else None
                return k if o is None else f"{slot_leaf_prefix(o)}/{k}"
            return (sorted(xlat(k) for k in leaves),
                    {xlat(k): v for k, v in shards.items()})
        return resolve

    # -- scheduler: admission / acceptance / eviction ----------------------

    def free_slots(self) -> List[int]:
        return [u for u in range(self.S) if self.slot_rid[u] is None]

    def check_admissible(self, rq: Request) -> None:
        """Reject a request whose worst-case KV footprint can NEVER fit
        (typed ``AdmissionError``) — the admission capacity guard.  Under
        paging this is the block-budget check; dense it is the ``max_len``
        check the engine used to silently overflow past."""
        need = len(rq.prompt) + 1 + rq.max_new_tokens
        if self.paged:
            nb = pgd.blocks_needed(len(rq.prompt), rq.max_new_tokens,
                                   self.block_size)
            if nb > self.max_blocks:
                raise AdmissionError(
                    f"rid={rq.rid}: needs {nb} blocks "
                    f"({need} positions), per-slot budget is "
                    f"{self.max_blocks} blocks ({self.max_len} positions)")
            if nb > self.alloc.capacity:
                raise AdmissionError(
                    f"rid={rq.rid}: needs {nb} blocks, whole pool holds "
                    f"{self.alloc.capacity}")
        elif need > self.max_len:
            raise AdmissionError(
                f"rid={rq.rid}: needs {need} positions "
                f"(prompt {len(rq.prompt)} + 1 + max_new "
                f"{rq.max_new_tokens}), slot capacity is {self.max_len}")

    def admit(self, rq: Request, slot: int, now_s: float = 0.0, *,
              interleave: bool = False) -> None:
        """Prefill + write the request into ``slot``; re-certify the
        touched canary units (partial refresh, both generations).

        Paged mode reserves the request's whole block budget up front
        (may raise ``PoolSaturated``) and, with ``interleave=True`` and a
        configured ``prefill_chunk``, only runs admission bookkeeping —
        the prompt is then prefilled chunk-at-a-time by ``_prefill_step``
        calls interleaved with decode engine steps."""
        self.check_admissible(rq)
        if self.paged:
            self._admit_paged(rq, slot, now_s, interleave=interleave)
            return
        batch = {"tokens": jnp.asarray(
            np.asarray(rq.prompt, np.int32)[None])}
        for k, v in rq.features.items():
            batch[k] = jnp.asarray(v)
        logits, sub = self._prefill(self.params, batch)
        if self._repl is not None:
            sub = jax.device_put(
                sub, jax.tree_util.tree_map(lambda _: self._repl, sub))
        replaying = bool(rq.log)
        if replaying:
            # prefix replay: the log IS the RSI — force the lane back
            # through its accepted tokens (bit-identical rebuild)
            t0 = rq.log[0]
            rq.forced = deque(rq.log[1:])
            self.report.replay_tokens += len(rq.log) - 1
        else:
            t0 = int(np.argmax(np.asarray(logits[0])))
            rq.log = [t0]
        self.cache, self.tok = self._admit_exec(
            self.cache, self.tok, sub, jnp.int32(t0), jnp.int32(slot))
        if self.canary is not None:
            # partial refresh: patch ONLY this slot's rows (in both
            # generations, no generation bump) so units of other slots
            # armed before this admission still verify
            self.canary.refresh(slot_view(self.cache, self.S),
                                keys=self._slot_keys[slot])
        self.slot_rid[slot] = rq.rid
        self._by_slot[slot] = rq
        rq.slot = slot
        rq.state = "active"
        if rq.t_admit_s < 0:
            rq.t_admit_s = now_s
        self.report.admissions += 1
        if self.verbose:
            kind = "replay" if replaying else "admit"
            print(f"[engine] {kind} rid={rq.rid} -> slot {slot} "
                  f"(log={len(rq.log)})")

    def _admit_paged(self, rq: Request, slot: int, now_s: float, *,
                     interleave: bool) -> None:
        """Paged admission: reserve the full block budget, zero the blocks
        (bit-exactness: freed blocks may hold non-finite bytes), wire the
        block table, and start the prefill.  All pool writes here are
        out-of-step, so the touched blocks' digests are refreshed before
        the next engine step can check them."""
        nb = pgd.blocks_needed(len(rq.prompt), rq.max_new_tokens,
                               self.block_size)
        bids = self.alloc.allocate(slot, nb)   # may raise PoolSaturated
        pad = np.zeros((self.max_blocks,), np.int32)
        pad[:nb] = bids
        self.pool = self._fns["zero"](self.pool,
                                      self._dev(jnp.asarray(pad)))
        self._bt_np[slot] = 0
        self._bt_np[slot, :nb] = bids
        self.bt = self._dev(jnp.asarray(self._bt_np))
        self.slot_rid[slot] = rq.rid
        rq.slot = slot
        rq.state = "active"
        if rq.t_admit_s < 0:
            rq.t_admit_s = now_s
        self.report.admissions += 1
        self._prefilling[slot] = {"rq": rq, "off": 0}
        # zero-on-alloc scattered through the padded index vector, which
        # repeats scratch block 0 — refresh it along with the real blocks
        self._refresh_blocks(set(bids) | {0})
        if self.verbose:
            kind = "replay" if rq.log else "admit"
            print(f"[engine] {kind} rid={rq.rid} -> slot {slot} "
                  f"({nb} blocks {bids})")
        if not interleave:
            while slot in self._prefilling:
                self._prefill_step(slot)

    def _prefill_step(self, slot: int) -> None:
        """Advance one slot's in-progress prefill by one unit: the whole
        prompt (monolithic) or one ``prefill_chunk``-sized chunk.  The
        produced KV rows are span-scattered into the slot's blocks and
        those blocks' digests refreshed; the final unit activates the
        lane."""
        st = self._prefilling[slot]
        rq = st["rq"]
        off = st["off"]
        P = len(rq.prompt)
        bs = self.block_size
        bt_row = self.bt[slot]
        owned = self.alloc.owned(slot)
        if self.prefill_chunk <= 0:
            # monolithic: reuse the dense prefill executable, then span-
            # scatter its (padded-to-max_len) cache — paged-vs-dense
            # bit-exact prefill by construction
            batch = {"tokens": jnp.asarray(
                np.asarray(rq.prompt, np.int32)[None])}
            for k, v in rq.features.items():
                batch[k] = jnp.asarray(v)
            logits, sub = self._prefill(self.params, batch)
            if self._repl is not None:
                sub = jax.device_put(
                    sub, jax.tree_util.tree_map(lambda _: self._repl, sub))
            self.pool = self._fns["span"](self.pool, sub["groups"], bt_row,
                                          jnp.int32(0), jnp.int32(P))
            touched = set(owned[: -(-P // bs)])
            st["off"] = P
        else:
            C = self.prefill_chunk
            valid = min(C, P - off)
            tokens = np.zeros((1, C), np.int32)
            tokens[0, :valid] = np.asarray(rq.prompt, np.int32)[
                off:off + valid]
            self.pool, logits = self._fns["chunk"](
                self.params, self.pool, bt_row, jnp.asarray(tokens),
                jnp.int32(off), jnp.int32(valid))
            touched = set(owned[off // bs: -(-(off + valid) // bs)])
            st["off"] = off + valid
        # padded scatter lanes redirect to scratch block 0
        self._refresh_blocks(touched | {0})
        if st["off"] >= P:
            del self._prefilling[slot]
            self._activate(rq, slot, P, logits)

    def _activate(self, rq: Request, slot: int, P: int, logits) -> None:
        """Prefill finished: install the first decode input and flip the
        lane active (fixed-shape dynamic-slice writes — no retrace)."""
        if rq.log:
            # prefix replay: the log IS the RSI
            t0 = rq.log[0]
            rq.forced = deque(rq.log[1:])
            self.report.replay_tokens += len(rq.log) - 1
        else:
            t0 = int(np.argmax(np.asarray(logits)[0]))
            rq.log = [t0]
        self.pos, self.tok, self.amask = self._fns["activate"](
            self.pos, self.tok, self.amask, jnp.int32(P), jnp.int32(t0),
            jnp.int32(slot))
        if self.canary is not None:
            self.canary.refresh(self._view(), keys=[self._pos_keys[slot]])
        self._by_slot[slot] = rq

    def _free(self, slot: int) -> None:
        self._slot_history[slot] = self.slot_rid[slot]
        self.slot_rid[slot] = None
        self._by_slot.pop(slot, None)
        if self.paged:
            self._prefilling.pop(slot, None)
            self.alloc.free(slot)
            self._bt_np[slot] = 0
            self.bt = self._dev(jnp.asarray(self._bt_np))
            self.amask = self._fns["deact"](self.amask, jnp.int32(slot))

    def _finish(self, rq: Request, now_s: float, dropped: bool = False
                ) -> None:
        rq.state = "dropped" if dropped else "done"
        rq.t_done_s = now_s
        self.report.per_request[rq.rid] = {
            "arrival_s": rq.arrival_s,
            "t_admit_s": rq.t_admit_s,
            "t_first_s": rq.t_first_s,
            "t_done_s": now_s,
            "e2e_s": now_s - rq.arrival_s,
            "n_out": rq.n_out,
            "replays": rq.replays,
            "retracted": rq.retracted,
            "dropped": dropped,
            "tokens": list(rq.log[1:]),
        }
        if dropped:
            self.report.dropped += 1
        else:
            self.report.completed += 1

    def _accept(self, tokens: np.ndarray, now_s: float) -> None:
        """Fold one step's payload into the active requests."""
        for u in sorted(self._by_slot):
            rq = self._by_slot[u]
            if rq.forced:
                # forced replay output — already in the log (accounted
                # before the fault); the lane just rebuilt one token
                rq.forced.popleft()
                continue
            rq.log.append(int(tokens[u]))
            self.report.tokens_out += 1
            if rq.t_first_s < 0:
                rq.t_first_s = now_s
            if rq.done:
                self._finish(rq, now_s)
                self._free(u)

    def handle_fault(self, report: Optional[FaultReport],
                     finite: np.ndarray, now_s: float,
                     queue: RequestQueue) -> List[int]:
        """Slot-isolated recovery: evict injured slots to prefix replay.
        Returns the evicted slot ids."""
        rep = self.report
        rep.faults_detected += 1
        nf = [u for u in self._by_slot if not finite[u]]
        plan = plan_serving_recovery(report, n_slices=self.K,
                                     nonfinite_slots=nf)
        occupied = (sorted(set(self._by_slot) | set(self._prefilling))
                    if self.paged else sorted(self._by_slot))
        victims = occupied if plan.scope == "engine" else plan.slots
        refresh_blocks: set = set()
        if self.paged:
            # snapshot BEFORE the frees below return blocks to the pool:
            # the injured (and victim-owned) blocks keep their corrupt
            # bytes until the next zero-on-alloc, and their units must
            # not double-fire meanwhile
            if report is not None:
                refresh_blocks |= set(report.injured_blocks())
            for u in victims:
                refresh_blocks |= set(self.alloc.owned(u))
        any_dropped = False
        for u in victims:
            rq = self._by_slot.get(u)
            if rq is None and self.paged and u in self._prefilling:
                rq = self._prefilling[u]["rq"]
            if rq is None:
                # occupant already completed/evicted — the fault window
                # may have overlapped its live tokens: SDC-risk telemetry
                rep.faults_on_free_slots += 1
                continue
            n = plan.retract if plan.retract is not None else rq.n_out
            removed = rq.retract(n)
            rep.retracted_tokens += removed
            rep.tokens_out -= removed
            rq.replays += 1
            rq.t_evicted_s = now_s
            rep.injured_rids.add(rq.rid)
            self._free(u)
            if rq.replays > self.max_replays:
                self._finish(rq, now_s, dropped=True)
                any_dropped = True
            else:
                queue.requeue_front(rq)
            if self.verbose:
                print(f"[engine] FAULT step {self.step_count} slot {u} "
                      f"rid={rq.rid} ({plan.reason}) — retract {removed}, "
                      f"replaying {len(rq.log) - 1} tokens")
        if self.paged:
            if (plan.scope == "slots" and not victims
                    and report is not None):
                # attribution landed only on unowned pool blocks — a
                # free-block flip evicts nobody (SDC-risk telemetry only)
                rep.faults_on_free_slots += 1
            if self.canary is not None:
                self._refresh_blocks(refresh_blocks)
                for u in victims:
                    self.canary.refresh(self._view(),
                                        keys=[self._pos_keys[u]])
        elif self.canary is not None and victims:
            # re-certify every evicted lane against its CURRENT (corrupt-
            # lineage) bytes: the lane keeps decoding garbage until the
            # next admission overwrites it, and its units must not
            # double-fire meanwhile (fault path only — one digest launch)
            keys = [k for u in victims for k in self._slot_keys[u]]
            self.canary.refresh(slot_view(self.cache, self.S), keys=keys)
        if not any_dropped:
            rep.faults_recovered += 1
        return victims

    # -- fault injection (evaluation adversary) ----------------------------

    def corrupt_slot(self, rng, slot: Optional[int] = None,
                     key: Optional[str] = None, bit: Optional[int] = None,
                     armed_only: bool = False) -> Tuple[int, str, int]:
        """Flip one bit of one element inside one slot's lane (the paper's
        single-bit-flip model scoped to the slot axis).  Prefers active
        slots.  Returns (slot, leaf key, bit).

        ``armed_only=True`` restricts the target to a (leaf, slot) unit
        inside the canary's currently **protected at-rest window** — the
        units armed from the previous step's output and checked by the
        NEXT engine step.  A rotating K-slice canary is a sampling
        detector (a random at-rest flip is caught with probability ~1/K
        per step, exactly as in training); armed-window targeting models
        the covered case deterministically, which is what the SLO storm
        and the slot-isolation tests need.  Random mode measures raw
        coverage instead.
        """
        if self.paged:
            return self._corrupt_paged(rng, slot, key, bit, armed_only)
        active = [u for u in range(self.S) if self.slot_rid[u] is not None]
        if armed_only and self.canary is not None and key is None:
            cls = self.step_count % self.K
            def cands(pool):
                out = []
                for u_ in pool:
                    if slot is not None and u_ != slot:
                        continue
                    for k_ in self._slot_keys[u_]:
                        if self.plan.index_of(k_) % self.K == cls:
                            out.append((u_, k_.split("/", 1)[1]))
                return out
            pool = cands(active) or cands(range(self.S))
            if pool:
                u, key = pool[rng.randrange(len(pool))]
                slot = u
        u = slot if slot is not None else rng.choice(active or
                                                     list(range(self.S)))
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.cache)
        catalog = [(i, leaf_key(p), x) for i, (p, x) in enumerate(flat)]
        if key is not None:
            picks = [c for c in catalog if c[1] == key]
            if not picks:
                raise KeyError(key)
            i, k, leaf = picks[0]
        else:
            sizes = [max(1, int(np.prod(x.shape[1:], dtype=np.int64)))
                     for _, _, x in catalog]
            total = sum(sizes)
            pick = rng.randrange(total)
            acc = 0
            for (i, k, leaf), sz in zip(catalog, sizes):
                acc += sz
                if pick < acc:
                    break
        per = max(1, int(np.prod(leaf.shape[1:], dtype=np.int64)))
        e = rng.randrange(per)
        width = _BIT_WIDTH.get(str(leaf.dtype), 32)
        b = bit if bit is not None else rng.randrange(width)
        leaves = [x for _, x in flat]
        leaves[i] = flip_bit(leaf, u * per + e, b)
        self.cache = jax.tree_util.tree_unflatten(treedef, leaves)
        self.report.faults_injected += 1
        rid = self.slot_rid[u]
        if rid is not None:
            self.report.injured_rids.add(rid)
        return u, k, b

    def corrupt_param(self, rng, key: Optional[str] = None,
                      bit: Optional[int] = None) -> Tuple[str, int]:
        """Flip one bit of one element of a parity-covered PARAM leaf —
        the at-rest weight-rot adversary `scrub_params` exists for.
        Preserves the leaf's device layout.  Returns (leaf key, bit)."""
        if self.parity_store is None:
            raise ValueError("corrupt_param requires parity=True")
        plan = self.parity_store.plan
        if key is None:
            key = plan.keys[rng.randrange(len(plan.keys))]
        leaves = dict(zip(plan.keys, plan.leaves(self.params)))
        leaf = leaves[key]
        size = max(1, int(np.prod(leaf.shape, dtype=np.int64)))
        e = rng.randrange(size)
        width = _BIT_WIDTH.get(str(leaf.dtype), 32)
        b = bit if bit is not None else rng.randrange(width)
        flipped = flip_bit(leaf, e, b)
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            flipped = jax.device_put(flipped, sharding)
        self.params = jax.tree_util.tree_map_with_path(
            lambda p, x: flipped if leaf_key(p) == key else x, self.params)
        self.report.faults_injected += 1
        return key, b

    def scrub_params(self) -> Dict:
        """At-rest integrity sweep over the params: verify every covered
        leaf against the load-time digests and XOR-reconstruct any
        injured shard from parity + survivors (no reload, no re-shard,
        O(bytes/D) moved).  Returns the scrub stats; repaired params are
        installed in place so subsequent decode steps use healthy
        weights."""
        if self.parity_store is None:
            raise ValueError("scrub_params requires parity=True")
        new_params, stats = self.parity_store.scrub(
            self.params, self._param_refs)
        if stats["repaired"]:
            self.params = new_params
            self.report.faults_detected += stats["repaired"]
            self.report.faults_recovered += stats["repaired"]
        stats["memory_bytes"] = self.parity_store.memory_bytes
        return stats

    def _owned_unit_keys(self, u: int) -> List[str]:
        """All canary plan keys a slot currently owns: its blocks' units
        plus its ``pos`` unit."""
        keys = [k for b in self.alloc.owned(u) for k in self._block_keys[b]]
        keys.append(self._pos_keys[u])
        return keys

    def _corrupt_paged(self, rng, slot, key, bit, armed_only
                       ) -> Tuple[int, str, int]:
        """Paged fault injector: the flip model is the same single-bit
        flip, but a 'slot' target is now the set of pool blocks the slot
        currently owns (plus its pos unit) — which is exactly the canary's
        (leaf, block) attribution granularity.  ``key`` accepts full plan
        keys (``blockNNNN/...`` or ``slotNNN/pos``) so tests can flip a
        specific — even unowned — block.  Returns (owning slot | -1,
        plan key, bit)."""
        active = [u for u in range(self.S) if self.slot_rid[u] is not None]
        if key is None:
            if armed_only and self.canary is not None:
                cls = self.step_count % self.K
                def cands(lanes):
                    out = []
                    for u_ in lanes:
                        if slot is not None and u_ != slot:
                            continue
                        for k_ in self._owned_unit_keys(u_):
                            if self.plan.index_of(k_) % self.K == cls:
                                out.append(k_)
                    return out
                picks = cands(active) or cands(range(self.S))
            else:
                lanes = ([slot] if slot is not None
                         else (active or list(range(self.S))))
                picks = [k_ for u_ in lanes
                         for k_ in self._owned_unit_keys(u_)]
            if not picks:
                picks = list(self._pos_keys)
            key = picks[rng.randrange(len(picks))]
        if key in self._pos_keys:
            u = self._pos_keys.index(key)
            b = bit if bit is not None else rng.randrange(32)
            self.pos = flip_bit(self.pos, u, b)
        else:
            blk = block_of_leaf(key)
            if blk is None:
                raise KeyError(key)
            rest = key.split("/", 1)[1]
            flat, treedef = jax.tree_util.tree_flatten_with_path(self.pool)
            for i, (p, x) in enumerate(flat):
                if leaf_key(p) == rest:
                    break
            else:
                raise KeyError(key)
            per = max(1, int(np.prod(x.shape[1:], dtype=np.int64)))
            e = rng.randrange(per)
            width = _BIT_WIDTH.get(str(x.dtype), 32)
            b = bit if bit is not None else rng.randrange(width)
            leaves = [lx for _, lx in flat]
            leaves[i] = flip_bit(x, blk * per + e, b)
            self.pool = jax.tree_util.tree_unflatten(treedef, leaves)
            u = self.alloc.owner.get(blk, -1)
        self.report.faults_injected += 1
        rid = self.slot_rid[u] if 0 <= u < self.S else None
        if rid is not None:
            self.report.injured_rids.add(rid)
        return u, key, b

    # -- driver ------------------------------------------------------------

    def run(self, requests: Sequence[Request], *, inject_every: int = 0,
            inject_rng=None, inject_armed_only: bool = True,
            clock=None) -> ServingReport:
        """Drive the engine until every request completes (or drops).

        ``inject_every`` > 0 runs the fault-storm adversary: one bit flip
        into a (preferably active) slot every N ACCEPTED tokens — by
        default into the canary's protected window (``inject_armed_only``;
        see ``corrupt_slot``), so every storm fault is detected and the
        recovery path is what gets measured.  Pinning the cadence to
        accepted tokens (not engine steps) keeps the storm survivable by
        construction: every fault is separated by N tokens of real
        progress, however long its replay takes.  ``clock`` overrides the
        engine clock (seconds; default: wall time since this call) — the
        SLO benchmark uses it for open-loop arrivals.
        """
        queue = RequestQueue(requests)
        rep = self.report
        rep.requests += len(requests)
        t_start = time.perf_counter()
        clock = clock or (lambda: time.perf_counter() - t_start)
        next_inject = rep.tokens_out + inject_every
        interleave = self.paged and self.prefill_chunk > 0
        while True:
            # admissions: fill free slots from the queue (iteration-level
            # scheduling — new requests enter every engine step)
            while True:
                free = self.free_slots()
                if not free:
                    break
                rq = queue.pop_ready(clock())
                if rq is None:
                    break
                evicted_at = rq.t_evicted_s
                try:
                    self.admit(rq, free[0], now_s=clock(),
                               interleave=interleave)
                except AdmissionError as err:
                    # permanent capacity overflow: typed rejection, not a
                    # silent cache overrun (and not a drop of anyone else)
                    rep.admission_rejected += 1
                    if self.verbose:
                        print(f"[engine] REJECT {err}")
                    self._finish(rq, clock(), dropped=True)
                    continue
                except PoolSaturated:
                    # transient block shortage: head-of-line waits for a
                    # running request to return its blocks
                    queue.requeue_front(rq)
                    break
                if evicted_at >= 0:
                    rep.recovery_ms.append(1e3 * (clock() - evicted_at))
                    rq.t_evicted_s = -1.0
            if self.paged and self._prefilling:
                # chunked prefill: one chunk per in-progress admission per
                # engine iteration, interleaved with the decode step below
                # so long prompts never stall the running batch
                for u in sorted(self._prefilling):
                    self._prefill_step(u)
            if not self._by_slot:
                if self.paged and self._prefilling:
                    continue
                nxt = queue.next_arrival()
                if nxt is None:
                    break
                # wait through the ENGINE clock: an injected (virtual)
                # clock supplies its own sleep, so idle waits advance
                # virtual time instead of busy-spinning wall time
                wait = max(0.0, nxt - clock())
                sleeper = getattr(clock, "sleep", None)
                (sleeper or time.sleep)(wait)
                continue

            if inject_every and rep.tokens_out >= next_inject:
                self.corrupt_slot(inject_rng, armed_only=inject_armed_only)
                next_inject = rep.tokens_out + inject_every

            t0 = time.perf_counter()
            tokens, finite, report = self.engine_step()
            rep.decode_ms.append(1e3 * (time.perf_counter() - t0))
            rep.engine_steps += 1
            now = clock()
            if report is not None or any(not finite[u]
                                         for u in self._by_slot):
                self.handle_fault(report, finite, now, queue)
            # healthy lanes keep the fault step's own tokens: lanes are
            # computationally independent, so a fault in slot u cannot
            # taint slot v's output
            self._accept(tokens, now)
        return rep
