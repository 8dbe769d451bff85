"""Resilient serving CLI — a thin driver over the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch iterpro-100m --smoke \
        --requests 8 --prompt-len 16 --gen 12 --inject 5

Everything serving-shaped lives in ``repro.serving``: the request queue,
the iteration-level scheduler over slot-major decode state, the per-slot
canary slice, and slot-isolated recovery (injured slots evict to prefix
replay; healthy slots keep decoding the very next engine step).  This
module only (a) turns CLI knobs into an engine + a request batch, (b)
seeds EVERY RNG in play — ``random``, numpy, and the JAX param key — from
one ``--seed`` so injection campaigns are reproducible run-to-run, and
(c) reports the engine's summary (now with p50/p99 percentiles next to
the means).

Composition knobs mirror the training path: ``--donate`` donates the
slot-major cache into the fused step (in-place KV update), detection is
ALWAYS in-step fused (1 launch + 1 scalar fault sync per engine step —
the ``--fused-detect`` flag of the old fixed-batch driver is accepted
for compatibility and is a no-op), and ``--mesh`` serves off a device
mesh with sharded params, a replicated slot-major cache, and a
shard-local canary.  KV memory is a paged block pool by default where
the family supports it (``--block-size`` sets the block granularity,
``--dense`` forces the old per-slot cache), and ``--prefill-chunk``
prefills long prompts chunk-at-a-time interleaved with decode steps.
"""

from __future__ import annotations

import argparse
import json
import random

import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import Request, ServingEngine
from repro.serving.engine import ServingReport   # noqa: F401 (re-export)

#: compat alias — the old fixed-batch driver exposed a ServeReport; the
#: engine's report (superset: percentiles, slot/SLO counters) replaces it
ServeReport = ServingReport


def make_requests(cfg, n_requests: int, prompt_len: int, gen_tokens: int,
                  nprng, arrivals=None):
    """Synthetic request batch: random prompts, optional open-loop
    arrival times (default: all at t=0, the closed-batch setting)."""
    vocab = cfg.model.vocab_size
    reqs = []
    for i in range(n_requests):
        reqs.append(Request(
            rid=i,
            prompt=nprng.integers(0, vocab, size=prompt_len).astype(np.int32),
            max_new_tokens=gen_tokens,
            arrival_s=float(arrivals[i]) if arrivals is not None else 0.0))
    return reqs


def serve(cfg, *, n_requests: int, prompt_len: int, gen_tokens: int,
          seed: int = 0, inject_every: int = 0, verbose: bool = True,
          canary_slices: int = 4, donate: bool = False,
          fused_detect: bool = False, mesh=None, n_slots: int = 0,
          paged=None, block_size: int = 8, prefill_chunk: int = 0,
          parity: bool = False):
    """Serve ``n_requests`` random prompts through the continuous-batching
    engine; returns the engine summary dict.

    ``inject_every`` > 0 flips one bit in a (preferably active) slot's
    decode state every N accepted tokens, targeted into the canary's
    protected window (see ``ServingEngine.corrupt_slot``) so the recovery
    path — slot eviction + prefix replay — is what gets exercised.
    ``fused_detect`` is accepted for CLI compatibility: the engine step is
    always in-step fused.

    The summary carries ``outputs`` (request id -> generated tokens) and
    ``injured`` (ids of the requests a fault touched).

    ``parity=True`` adds at-rest protection for the STATIC params: one
    XOR parity build at load time (1/D memory), then an end-of-run
    ``scrub_params`` sweep that detects and repairs silent weight rot in
    O(bytes/D) without reloading the checkpoint.  With ``inject_every``
    set, one param bit is also flipped after the run so the smoke
    exercises the repair (reported under ``"parity"`` in the summary).
    """
    del fused_detect  # engine detection is always in-step fused
    # one seed, every RNG: stdlib `random` (injection storm), numpy
    # (prompts), and the JAX param key (engine init) — plus the global
    # singletons, so user code downstream of serve() is reproducible too
    random.seed(seed)
    np.random.seed(seed % 2**32)
    rng = random.Random(seed)
    nprng = np.random.default_rng(seed)

    ctx = None
    if mesh:
        from repro.launch.mesh import make_context
        ctx = make_context(mesh)

    slots = n_slots or min(4, max(1, n_requests))
    eng = ServingEngine(
        cfg, n_slots=slots, max_len=prompt_len + gen_tokens + 1,
        canary_slices=canary_slices, donate=donate, ctx=ctx, seed=seed,
        # serve() promises every request completes (prefix replay always
        # works) — the drop bound is an SLO-benchmark knob, not a CLI one
        max_replays=10**6, verbose=verbose, paged=paged,
        block_size=block_size, prefill_chunk=prefill_chunk, parity=parity)
    reqs = make_requests(cfg, n_requests, prompt_len, gen_tokens, nprng)
    eng.warm()
    rep = eng.run(reqs, inject_every=inject_every, inject_rng=rng)
    out = rep.summary()
    # every request's generated tokens, and which requests a fault touched:
    # what a caller compares against a fault-free run on the same seed
    out["outputs"] = {rid: r["tokens"]
                      for rid, r in sorted(rep.per_request.items())}
    out["injured"] = sorted(rep.injured_rids)
    if parity:
        if inject_every:
            # at-rest weight-rot adversary: flip one param bit after the
            # run so the scrub below demonstrates detection + XOR repair
            eng.corrupt_param(rng)
        out["parity"] = eng.scrub_params()
    if verbose:
        print(json.dumps(out, indent=1))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="iterpro-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds random, numpy AND the JAX param key")
    ap.add_argument("--slots", type=int, default=0,
                    help="batch slots (0: min(4, requests))")
    ap.add_argument("--canary-slices", type=int, default=4)
    ap.add_argument("--inject", type=int, default=0,
                    help="flip one bit in a slot's decode state every N "
                         "accepted tokens")
    ap.add_argument("--donate", action="store_true",
                    help="donate the slot-major cache into the fused step "
                         "(in-place KV update)")
    ap.add_argument("--fused-detect", action="store_true",
                    help="compat no-op: detection is always in-step fused")
    ap.add_argument("--block-size", type=int, default=8,
                    help="paged-KV block size in token positions")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prefill long prompts in chunks of this many "
                         "tokens, interleaved with decode steps (0: "
                         "monolithic prefill)")
    ap.add_argument("--dense", action="store_true",
                    help="force the dense per-slot KV cache (paged pool "
                         "is the default where the family supports it)")
    ap.add_argument("--mesh", default=None,
                    help="serve off a device mesh, e.g. '4,2' (CPU repro: "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8); params shard, the slot cache "
                         "replicates, the canary goes shard-local")
    ap.add_argument("--parity", action="store_true",
                    help="at-rest XOR parity over the static params (1/D "
                         "memory): an end-of-run scrub detects and "
                         "repairs silent weight rot in O(bytes/D) with "
                         "no checkpoint reload")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    serve(cfg, n_requests=args.requests, prompt_len=args.prompt_len,
          gen_tokens=args.gen, seed=args.seed, inject_every=args.inject,
          canary_slices=args.canary_slices, donate=args.donate,
          fused_detect=args.fused_detect, mesh=args.mesh,
          n_slots=args.slots, paged=False if args.dense else None,
          block_size=args.block_size, prefill_chunk=args.prefill_chunk,
          parity=args.parity)


if __name__ == "__main__":
    main()
