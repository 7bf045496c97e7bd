"""The shared un-fakeable wall-clock measurement protocol (round 4).

One implementation, used by both `bench.py` (the driver headline) and
`tools/bench_suite.py` (the BASELINE.md tracked configs), because this
logic is safety-critical: round 3's headline was a ~26,000x timing
artifact caused by timing dispatch instead of compute (BENCH_NOTES.md
round-4 postmortem).  The protocol:

1. Every array the caller's convergence/health asserts consume is pulled
   to host INSIDE the timed window (`check`'s np.asarray device->host
   copies are the completion proof — the bytes cannot exist until the
   device computed them).
2. >= `reps` repetitions; median + min/max reported.
3. One fully-synchronous cross-check rep (scalar readback after every
   chunk, immune to async-dispatch artifacts).  If the async median
   implies more than `sync_tolerance` x the synchronous rate, the async
   number is distrusted: the synchronous rate is emitted instead and the
   result is flagged ``"crosscheck": "sync_override"``.
"""

from __future__ import annotations

import sys
import time

import numpy as np


def timed_chunks(step, init, steps, batch, chunk_ms, check, reps=3,
                 sync_tolerance=2.0):
    """Measure `steps` x `chunk_ms` of simulation under the protocol above.

    step:  (nets, ps) -> (nets, ps), jitted chunk advance
    init:  () -> (nets, ps) fresh initial state
    batch: number of parallel runs inside `step` (for the aggregate rate)
    check: (nets, ps) -> dict of host-side facts; must np.asarray every
           array its asserts consume (that IS the materialization), and
           must raise on convergence/drop failures.

    Returns a result dict: value (agg sim-ms/s), reps, wall stats,
    sync_rate, crosscheck, plus `check`'s facts.
    """
    def one_rep(sync):
        nets, ps = init()
        # Materialize init outside the window via a host copy (not a
        # possibly-broken block call); leakage would only make the
        # number worse.
        np.asarray(nets.time)
        t0 = time.perf_counter()
        for _ in range(steps):
            nets, ps = step(nets, ps)
            if sync:
                # Scalar device->host per chunk: the chunk is provably
                # finished before the next dispatch.
                float(np.asarray(nets.time).sum())
        facts = check(nets, ps)             # device->host inside window
        wall = time.perf_counter() - t0
        return wall, facts

    # Compile + warm with ONE chunk (same jitted executable), then reset.
    nets, ps = init()
    nets, ps = step(nets, ps)
    np.asarray(nets.time)

    walls = [one_rep(sync=False)[0] for _ in range(max(1, reps))]
    sync_wall, facts = one_rep(sync=True)
    med = float(np.median(walls))
    total = batch * steps * chunk_ms
    async_rate, sync_rate = total / med, total / sync_wall
    if async_rate > sync_tolerance * sync_rate:
        # Measured throughput has varied between runs (observed 2.4x
        # between IDENTICAL sequential batches); before distrusting the
        # async number, give the synchronous path one more chance to
        # land on a healthy patch.  Taking the best of two sync walls is
        # honest: each is a real measured completion, and variance only
        # ever makes a sync rep slower, never faster than the device.
        sync_wall2, _ = one_rep(sync=True)
        sync_wall = min(sync_wall, sync_wall2)
        sync_rate = total / sync_wall
    out = {
        "value": round(async_rate, 1),
        "unit": "sim_ms/s",
        "reps": len(walls),
        "wall_median_s": round(med, 4),
        "wall_min_s": round(min(walls), 4),
        "wall_max_s": round(max(walls), 4),
        "sync_rate": round(sync_rate, 1),
        "crosscheck": "ok",
        **facts,
    }
    if async_rate > sync_tolerance * sync_rate:
        # r3 failure mode: async dispatch "finished" 26,000x faster than
        # the device could compute.  Publish the provably-synchronous
        # number and say so, rather than an artifact.
        print(f"measure: CROSS-CHECK FAILED — async median implies "
              f"{async_rate:.1f} sim-ms/s but the synchronous pass "
              f"measured {sync_rate:.1f} ({async_rate / sync_rate:.1f}x); "
              f"emitting the synchronous rate", file=sys.stderr)
        out["crosscheck"] = "sync_override"
        out["value"] = round(sync_rate, 1)
    return out
