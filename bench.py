"""Benchmark entry point — prints ONE JSON line with the headline metric.

Headline: wall-clock for the reference's default Handel scenario
(HandelScenarios.java:61-123 — 2048 nodes, 10% dead, threshold 0.99*live,
pairing 4 ms, period 20 ms, fastPath 10) to reach ALL live nodes done,
reported as aggregate simulated-ms/sec across a batch of seeds (the
vmap-over-seeds execution mode that is this framework's whole point).

vs_baseline: the reference publishes no wall-clock numbers (BASELINE.md);
the ratio is against the driver's budget of 10k aggregate sim-ms/s for this
config (≈ 10 full 2048-node Handel runs per wall-second).

Env overrides for smoke runs: WTPU_BENCH_NODES, WTPU_BENCH_SEEDS,
WTPU_BENCH_MS; WTPU_BENCH_MODE=cardinal benches the O(N*L) tier-3
variant (models/handel_cardinal.py) for 100k-class node counts.
WTPU_FAST_FORWARD=1 swaps the dense scan for the quiet-window
fast-forwarding engine (core/network.fast_forward_chunk — bit-identical,
tests/test_fast_forward.py) and reports `skipped_ms`/`jump_count`/
`skip_rate` so the speedup is attributable.  WTPU_BENCH_PROTO=
pingpong|dfinity benches the quiet-heavy protocols where skipping, not
node width, is the lever (skip-rate governs the win — SCALE.md).
Every emitted line carries an `engine_metrics` block (wittgenstein_tpu/
obs — on-device per-interval telemetry from an un-timed bit-identical
instrumented pass; schema in BENCH_NOTES.md).  WTPU_METRICS=0 skips it;
WTPU_METRICS_EACH_MS / WTPU_METRICS_SEEDS size it.  WTPU_TRACE=1 adds a
`trace` block from an un-timed flight-recorder pass (message-level
event counts + truncation accounting; schema in BENCH_NOTES.md r9);
WTPU_TRACE_CAP sizes the ring — an over-small capacity (< 1 row per
simulated ms) REFUSES loudly instead of emitting a silently truncated
trace, mirroring the invalid-superstep refusal.  Every line also
carries an `audit` block (wittgenstein_tpu/obs/audit.py — an un-timed
pass with the compiled conservation-law monitors ON; a violated
verdict is loud in the block AND on stderr); WTPU_AUDIT=0 skips it.
WTPU_LEDGER=0 skips the per-line `RunManifest` provenance row appended
under reports/ledger/ (obs/ledger.py; schema in BENCH_NOTES.md r10).
WTPU_PALLAS_ROUTE=1 swaps the mailbox-ring sort/scatter binning for the
fused Pallas routing megakernel (ops/pallas_route.py — bit-identical,
interpret mode on CPU); every line records `route_kernel` (xla|pallas)
plus the measured `sort_ops_per_sim_ms`/`scatter_ops_per_sim_ms` of the
compiled chunk (WTPU_ROUTE_STATS=0 skips the count; schema in
BENCH_NOTES.md r12).
The WTPU_* scenario knobs are captured as ONE `ScenarioSpec`
(wittgenstein_tpu/serve/spec.py — the request plane's config object);
main() reads its knobs back out of the spec and the ledger row's
config digest is the spec digest, so bench, bench_suite and serve
share one config path.

The bench runs on whatever backend JAX selects (JAX_PLATFORMS) and
names it in the line (`platform`, `device`); it never switches backend,
re-execs or shrinks the seed batch on its own.  A failed instrumented
pass fails the run.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np


def _ff_step_wrapper(ff_step):
    """Adapt a stats-bearing fast-forward chunk ``(nets, ps) -> (nets,
    ps, stats)`` to the measurement protocol's 2-tuple interface,
    stashing the per-chunk stats (device arrays — appending forces no
    sync, so timed reps stay fully async).  `_ff_stats` sums the LAST
    rep's worth afterwards: the runs are deterministic, so every rep's
    skip accounting is identical."""
    def step(nets, ps):
        nets, ps, st = ff_step(nets, ps)
        step.ff_stats.append(st)
        return nets, ps

    step.ff_stats = []
    return step


def _ff_stats(step, steps, chunk_ms):
    """Skip accounting for the emitted JSON line (empty when the step is
    not a fast-forward wrapper).  skip_rate is skipped-ms over the
    per-run simulated span — the quantity that governs the win."""
    stats = getattr(step, "ff_stats", None)
    if not stats:
        return {}
    tail = stats[-steps:]
    skipped = sum(int(np.asarray(s["skipped_ms"])) for s in tail)
    jumps = sum(int(np.asarray(s["jump_count"])) for s in tail)
    # Batched engines report lockstep-batch skips (one count for all
    # seeds); the per-run span is steps * chunk either way.
    return {"fast_forward": True, "skipped_ms": skipped,
            "jump_count": jumps,
            "skip_rate": round(skipped / max(1, steps * chunk_ms), 3)}


def _collect_engine_metrics(proto, seeds, total_ms, fast_forward=False):
    """Un-timed instrumented pass for the JSON line's `engine_metrics`
    block (wittgenstein_tpu/obs; schema in BENCH_NOTES.md).

    Runs AFTER the timed reps so the measured hot path stays exactly
    the uninstrumented engine (the `metrics_zero_cost` analysis rule
    pins that the OFF build carries no residue); the instrumented pass
    is bit-identical on the simulation trajectory (tests/test_obs.py),
    so the block describes the same runs the bench timed.  Engine
    dispatch mirrors the bench (batched seed-folded when eligible, else
    vmapped per-ms; fast-forward twins under WTPU_FAST_FORWARD=1).
    WTPU_METRICS=0 skips the pass; WTPU_METRICS_EACH_MS /
    WTPU_METRICS_SEEDS size it.  A failed pass fails the run."""
    from wittgenstein_tpu.obs import (MetricsFrame, MetricsSpec,
                                      engine_metrics_block)
    from wittgenstein_tpu.obs import engine as obs_engine

    each = _int_env("WTPU_METRICS_EACH_MS", max(2, (total_ms // 10) & ~1))
    spec = MetricsSpec(stat_each_ms=each + (each % 2))
    mseeds = min(seeds, _int_env("WTPU_METRICS_SEEDS", 4))
    ms = total_ms + (total_ms % 2)
    nets, ps = jax.vmap(proto.init)(jnp.arange(mseeds, dtype=jnp.int32))
    try:
        if fast_forward:
            run = jax.jit(obs_engine.fast_forward_chunk_batched_metrics(
                proto, ms, spec))
        else:
            run = jax.jit(obs_engine.scan_chunk_batched_metrics(
                proto, ms, spec))
    except ValueError:
        from wittgenstein_tpu.core.network import fast_forward_ok
        if fast_forward and fast_forward_ok(proto):
            run = jax.jit(obs_engine.fast_forward_chunk_metrics(
                proto, ms, spec, seed_axis=True))
        else:
            run = jax.jit(jax.vmap(obs_engine.scan_chunk_metrics(
                proto, ms, spec)))
    frame = MetricsFrame.from_carry(spec, run(nets, ps)[-1])
    return engine_metrics_block(frame, extra={"metrics_seeds": mseeds})


def _maybe_engine_metrics(res, proto, seeds, total_ms, fast_forward=False):
    if os.environ.get("WTPU_METRICS", "1") != "0":
        res["engine_metrics"] = _collect_engine_metrics(
            proto, seeds, total_ms, fast_forward=fast_forward)
    return _maybe_engine_trace(res, proto, total_ms,
                               fast_forward=fast_forward)


def _collect_engine_trace(proto, total_ms, cap, fast_forward=False):
    """Un-timed flight-recorder pass for the JSON line's `trace` block
    (wittgenstein_tpu/obs/trace.py; schema in BENCH_NOTES.md r9).

    Single seed, the dense traced engine (or its fast-forward twin
    under WTPU_FAST_FORWARD=1): runs AFTER the timed reps — the
    measured hot path stays the uninstrumented engine (`trace_zero_cost`
    rule) and the traced pass is bit-identical on the trajectory
    (tests/test_trace.py), so the block describes the same run the
    bench timed.  A failed pass fails the run (the CAPACITY refusal
    happens earlier, in `_check_trace_cap` before the timed reps)."""
    from wittgenstein_tpu.core.network import fast_forward_ok
    from wittgenstein_tpu.obs import TraceFrame, TraceSpec, trace_block
    from wittgenstein_tpu.obs.trace import (fast_forward_chunk_trace,
                                            scan_chunk_trace)

    spec = TraceSpec(capacity=cap)
    net, ps = proto.init(jnp.asarray(0, jnp.int32))
    if fast_forward and fast_forward_ok(proto):
        run = jax.jit(fast_forward_chunk_trace(proto, total_ms, spec))
    else:
        run = jax.jit(scan_chunk_trace(proto, total_ms, spec))
    frame = TraceFrame.from_carry(spec, run(net, ps)[-1])
    return trace_block(frame, extra={"trace_seeds": 1})


def _check_trace_cap(total_ms):
    """The PR-4 invalid-K pattern: refuse loudly rather than emit a
    mislabeled artifact — a ring smaller than one event row per
    simulated ms is guaranteed to truncate from the first busy stretch,
    and a benchmark line carrying a near-empty `trace` block would read
    as "this run was quiet" when it wasn't.  Called BEFORE the timed
    reps (both values are known up front) so an invalid env pair fails
    in milliseconds instead of after a whole timed session."""
    if os.environ.get("WTPU_TRACE") != "1":
        return
    cap = _int_env("WTPU_TRACE_CAP", 1 << 16)
    if cap < total_ms:
        raise ValueError(
            f"WTPU_TRACE=1 with WTPU_TRACE_CAP={cap} over {total_ms} "
            f"simulated ms cannot hold even one event row per ms: the "
            "ring would truncate silently from the first busy interval. "
            f"Fix: raise WTPU_TRACE_CAP to >= {total_ms} (the default "
            "65536 fits most bench spans), lower WTPU_BENCH_MS, or drop "
            "WTPU_TRACE")


def _maybe_engine_trace(res, proto, total_ms, fast_forward=False):
    if os.environ.get("WTPU_TRACE") != "1":
        return _maybe_engine_audit(res, proto, total_ms,
                                   fast_forward=fast_forward)
    _check_trace_cap(total_ms)
    res["trace"] = _collect_engine_trace(
        proto, total_ms, _int_env("WTPU_TRACE_CAP", 1 << 16),
        fast_forward=fast_forward)
    return _maybe_engine_audit(res, proto, total_ms,
                               fast_forward=fast_forward)


def _collect_engine_audit(proto, total_ms, fast_forward=False):
    """Un-timed invariant-audit pass for the JSON line's `audit` block
    (wittgenstein_tpu/obs/audit.py; schema in BENCH_NOTES.md r10).

    Single seed, the dense audited engine (or its fast-forward twin
    under WTPU_FAST_FORWARD=1): runs AFTER the timed reps — the
    measured hot path stays the uninstrumented engine (`audit_zero_cost`
    rule) and the audited pass is bit-identical on the trajectory
    (tests/test_audit.py), so the verdict describes the same run the
    bench timed.  A VIOLATED verdict is loud in the block
    (``"clean": false`` + the first-violation record) — the whole point
    of the plane is that a benchmark number over a broken run announces
    itself.  A failed pass fails the run."""
    from wittgenstein_tpu.core.network import fast_forward_ok
    from wittgenstein_tpu.obs.audit import AuditSpec
    from wittgenstein_tpu.obs.audit_report import audit_block, audit_variant

    variant = ({"fast_forward": True}
               if fast_forward and fast_forward_ok(proto) else {})
    report, _ = audit_variant(proto, total_ms, variant, AuditSpec())
    if not report.clean:
        print(f"bench: AUDIT VIOLATIONS in the instrumented pass:\n"
              f"{report.format()}", file=sys.stderr)
    return audit_block(report, extra={"audit_seeds": 1})


def _maybe_engine_audit(res, proto, total_ms, fast_forward=False):
    if os.environ.get("WTPU_AUDIT", "1") != "0":
        res["audit"] = _collect_engine_audit(proto, total_ms,
                                             fast_forward=fast_forward)
    return _maybe_chaos(res, proto, total_ms)


def _collect_chaos(proto, total_ms):
    """Un-timed chaos-plane pass for the JSON line's `chaos` block
    (wittgenstein_tpu/chaos; schema in BENCH_NOTES.md r13).

    ``WTPU_CHAOS`` carries a `FaultSchedule` as inline JSON; a
    MALFORMED or out-of-range schedule refuses loudly (the
    WTPU_TRACE_CAP pattern — a silently dropped schedule would emit a
    `chaos` block for a run that never saw adversity).  The pass wraps
    the bench protocol in `ChaosProtocol`, runs the dense audited
    engine over the FAULTED trajectory (audit verdicts must stay clean
    under churn/partition — a violation is loud in the block and on
    stderr), then one fault-free twin pass for the impact deltas
    (done/live/message totals, faulted vs baseline).  Single seed,
    after the timed reps — the measured hot path never carries the
    wrap."""
    from wittgenstein_tpu.chaos import (ChaosProtocol, FaultSchedule,
                                        impact_summary)
    from wittgenstein_tpu.obs.audit import AuditSpec
    from wittgenstein_tpu.obs.audit_report import audit_block, audit_variant

    sched = FaultSchedule.from_json(os.environ["WTPU_CHAOS"]).validate(
        n=proto.cfg.n, sim_ms=total_ms)
    spec = AuditSpec()
    report, (nets, _) = audit_variant(ChaosProtocol(proto, sched),
                                      total_ms, {"superstep": 1}, spec)
    _, (nets0, _) = audit_variant(proto, total_ms, {"superstep": 1}, spec)
    if not report.clean:
        print(f"bench: AUDIT VIOLATIONS under the chaos schedule:\n"
              f"{report.format()}", file=sys.stderr)
    return {"schedule": sched.counts(),
            "transitions": len(sched.transition_times()),
            "audit": audit_block(report),
            "faulted": impact_summary(nets),
            "baseline": impact_summary(nets0)}


def _maybe_chaos(res, proto, total_ms):
    raw = os.environ.get("WTPU_CHAOS")
    if raw and raw != "0":
        res["chaos"] = _collect_chaos(proto, total_ms)
    return res


def _route_stats(base, init, eff_ss, engine):
    """`route_kernel` (xla|pallas) + the MEASURED sort/scatter ops per
    simulated ms of the compiled chunk, for the JSON metric line —
    the number the `superstep_amortization` analysis rule ratchets,
    read off the program the bench actually runs (post-optimization
    HLO scan bodies, counted by the rule's own parser).  With the
    Pallas routing megakernel ON (`WTPU_PALLAS_ROUTE=1`) the counts
    drop to ~0: the binning lives inside one custom call.  The AOT
    lowering compiles the same program the timed reps use (persistent
    cache makes the second compile ~free); `WTPU_ROUTE_STATS=0`
    skips.  A failed count fails the run."""
    import types

    from wittgenstein_tpu.analysis import hlo as _hlo
    from wittgenstein_tpu.analysis import rules_superstep as _rs
    from wittgenstein_tpu.ops.pallas_route import route_enabled
    out = {"route_kernel": "pallas" if route_enabled() else "xla"}
    if os.environ.get("WTPU_ROUTE_STATS", "1") == "0":
        return out
    txt = jax.jit(base).lower(*jax.eval_shape(init)).compile().as_text()
    if _hlo.scan_bodies(txt):
        m = _rs.measure(types.SimpleNamespace(
            hlo_text=txt, ms_per_iter=max(1, eff_ss), engine=engine))
        out["sort_ops_per_sim_ms"] = m["sort_ops_per_ms"]
        out["scatter_ops_per_sim_ms"] = m["scatter_ops_per_ms"]
    return out


def _latency_env():
    """The run's latency-model registry name, or None.  One resolution
    rule for every bench branch: legacy WTPU_BENCH_LATENCY wins over
    canonical WTPU_LATENCY (ScenarioSpec.from_env refuses the
    double-set loudly), and '0' means unset — the from_env convention."""
    lat = (os.environ.get("WTPU_BENCH_LATENCY")
           or os.environ.get("WTPU_LATENCY"))
    return lat if lat and lat != "0" else None


def _handel_setup(n, seeds, sim_ms, chunk, mode, horizon, inbox_cap,
                  superstep, box_split=1, route_stats=False):
    """Build the benchmark's (step, init, steps, check, proto,
    superstep, engine) tuple for the reference default Handel scenario
    — `engine` names the dispatch actually taken ("batched" /
    "fast_forward" / "vmapped"), recorded in the JSON line and the
    ledger row so provenance never re-derives it."""
    import dataclasses

    from wittgenstein_tpu.core.network import scan_chunk
    from wittgenstein_tpu.models.handel import Handel

    down = n // 10
    # Ring sizing is engine CAPACITY, not protocol semantics: the asserts
    # below require zero drops/clamps/evictions, so an undersized ring
    # fails loudly rather than silently changing behavior.  hz 256 /
    # inbox 12 run drop-free at the headline config (PR 21 chip run).
    kw = dict(horizon=horizon, inbox_cap=inbox_cap)
    if mode == "cardinal" and n > 32768:
        # Tier-2: bounded queue + ring keep the state in one chip's HBM
        # (per-plane int32 flat indexing now reaches ~1M nodes at
        # 256*n*8; memory binds first — SCALE.md).  inbox_cap is honored
        # as passed (main() picks a tier-appropriate default); horizon
        # never exceeds the tier bound.  Use tools/cardinal_1m.py (mesh
        # sharding + a bounded-latency model) for 1M-class runs.
        # queue_cap 16: cardinal queue columns are [N, Q] int32 (no
        # [N, Q, W] sig rows), so the larger cap costs ~4 MB at 65k and
        # avoids the evictions queue_cap=8 shows there.
        kw = dict(queue_cap=16, inbox_cap=inbox_cap,
                  horizon=min(horizon, 256))
    if mode == "exact":
        # Tier-2 exact-mode on one chip: hashed emission drops the
        # O(N^2) [N, 2N] stored lists (2.1 GB at 16k) while keeping
        # reference-exact aggregation semantics; WTPU_BENCH_POOL=0
        # additionally drops the [N, R, W] send-time snapshot pool.
        if os.environ.get("WTPU_BENCH_EMISSION"):
            kw["emission_mode"] = os.environ["WTPU_BENCH_EMISSION"]
        if os.environ.get("WTPU_BENCH_POOL"):
            kw["snapshot_pool"] = os.environ["WTPU_BENCH_POOL"] == "1"
        if os.environ.get("WTPU_BENCH_QUEUE"):
            kw["queue_cap"] = _int_env("WTPU_BENCH_QUEUE", 16)
        if os.environ.get("WTPU_BENCH_STATE_SPLIT"):
            # q_sig node-range pieces (HandelState.q_sig): keep each
            # piece's flat int32 index range and delivery transients
            # bounded at the 32k-exact tier.
            kw["state_split"] = _int_env("WTPU_BENCH_STATE_SPLIT", 1)
        if os.environ.get("WTPU_BENCH_PALLAS"):
            kw["pallas_merge"] = os.environ["WTPU_BENCH_PALLAS"] == "1"
    # WTPU_BENCH_LATENCY / WTPU_LATENCY override the latency model by
    # registry name — the floor-rich A/B lever (e.g.
    # "NetworkFixedLatency(16)" licenses the superstep-K ladder; the
    # default distance model floors at 2).  WTPU_LATENCY is the
    # canonical spelling captured into the spec's `latency_model` field
    # (ScenarioSpec.from_env refuses unknown names AND a double-set
    # loudly), so the ledger row records the model this setup builds.
    lat = _latency_env()
    if lat:
        kw["network_latency_name"] = lat
    proto = Handel(node_count=n, threshold=int(0.99 * (n - down)),
                   nodes_down=down, pairing_time=4, level_wait_time=50,
                   dissemination_period_ms=20, fast_path=10, mode=mode,
                   **kw)
    if box_split > 1:
        # Node-range ring sub-planes (bit-identical layout change): keeps
        # each sub-plane's flat int32 index below 2^31 as node count and
        # ring size grow.
        proto.cfg = dataclasses.replace(proto.cfg, box_split=box_split)
    # t0_mod=0: runs start at time 0 and `chunk` is a multiple of the
    # schedule lcm, so the phase-specialized scan applies (bit-identical,
    # tests/test_phase_hints.py) — masked verification/dissemination work
    # is only traced on the ms where it can fire.  WTPU_BENCH_SPEC=0
    # forces the plain per-ms scan (debug/bisect knob).
    lcm = getattr(proto, "schedule_lcm", None)
    if os.environ.get("WTPU_BENCH_SPEC") == "0":
        lcm = None
    # WTPU_FAST_FORWARD=1: the quiet-window while-loop engine replaces
    # the dense scan AND the static phase hints (the oracle skips the
    # hint-masked ms dynamically; the two cannot compose — see
    # network.check_chunk_config).  Bit-identical either way.
    fast_forward = os.environ.get("WTPU_FAST_FORWARD") == "1"
    if fast_forward:
        lcm = None
    t0 = 0 if (lcm and chunk % lcm == 0) else None
    # superstep="auto": the largest K the K-aware gate proves for this
    # protocol/chunk (latency floor + 1, horizon/chunk divisibility —
    # core/network.pick_superstep); an explicit K is passed through to
    # the gate, which raises with a remedy instead of silently demoting
    # (a mislabeled A/B is worse than a refused one).
    from wittgenstein_tpu.core.network import pick_superstep
    if superstep == "auto":
        superstep = pick_superstep(proto, chunk, t0=0,
                                   lcm=lcm if t0 is not None else None)
    else:
        superstep = int(superstep)
    donate = os.environ.get("WTPU_BENCH_DONATE") == "1"
    # Batched (seed-folded) engine is the default: measured 92.3 vs 81.0
    # agg sim-ms/s at the headline config (BENCH_NOTES.md r4), bit
    # identical.  WTPU_BENCH_BATCHED=0 falls back to the vmapped path;
    # superstep=1 falls back automatically UNLESS batched was requested
    # EXPLICITLY, which would silently mislabel a superstep A/B — refuse
    # loudly instead.
    env_batched = os.environ.get("WTPU_BENCH_BATCHED")
    if env_batched == "1" and superstep < 2:
        raise ValueError("WTPU_BENCH_BATCHED=1 implies superstep >= 2 "
                         "(core/batched.py is hard-wired to the fused "
                         "K-ms window engine)")
    ff_base = None          # stats-bearing (nets, ps) -> (nets, ps, stats)
    engine = "fast_forward" if fast_forward else "vmapped"
    if (env_batched or "1") == "1" and superstep >= 2:
        engine = "fast_forward" if fast_forward else "batched"
        # Seed-folded mailbox machinery (core/batched.py): avoids the
        # vmapped scatter's per-seed serialization (PROFILE_r4.md) —
        # bit-identical (tests/test_batched.py).
        from wittgenstein_tpu.core.batched import (
            fast_forward_chunk_batched, scan_chunk_batched)
        # Same-process A/B knob for the plane-ordering barrier
        # (bit-identical either way; tools/ab_plane_barrier.py).
        barrier = os.environ.get("WTPU_PLANE_BARRIER", "1") != "0"
        if fast_forward:
            base = ff_base = fast_forward_chunk_batched(
                proto, chunk, plane_barrier=barrier, superstep=superstep)
        else:
            base = scan_chunk_batched(proto, chunk, t0_mod=t0,
                                      plane_barrier=barrier,
                                      superstep=superstep)
        step = jax.jit(base)
    else:
        from wittgenstein_tpu.core.network import fast_forward_chunk
        if fast_forward:
            # The vmapped fast-forward engine fuses the while body into
            # the same K-ms windows (K-aligned jumps) — no mislabeled
            # A/B: the superstep value is honored on every path.
            base = ff_base = fast_forward_chunk(proto, chunk,
                                                seed_axis=True,
                                                superstep=superstep)
        else:
            base = jax.vmap(scan_chunk(proto, chunk, t0_mod=t0,
                                       superstep=superstep))
        step = jax.jit(base)
    steps = max(1, -(-sim_ms // chunk))

    def init(seed0=0):
        return jax.vmap(proto.init)(
            seed0 + jnp.arange(seeds, dtype=jnp.int32))

    if donate:
        # Whole-state donation: the outputs reuse the inputs' buffers, so
        # configs whose carry would otherwise double in HBM fit one chip
        # (headline: 2.63 GB of outputs alias the arguments; bit-identical
        # on a v5e, PR 21 chip run).
        step = jax.jit(base, donate_argnums=(0, 1))

    if ff_base is not None:
        step = _ff_step_wrapper(step)

    def check(nets, ps):
        done_at = np.asarray(nets.nodes.done_at)
        downs = np.asarray(nets.nodes.down)
        dropped = int(np.asarray(nets.dropped).sum())
        bc_dropped = int(np.asarray(nets.bc_dropped).sum())
        clamped = int(np.asarray(nets.clamped).sum())
        evicted = int(np.asarray(ps.evicted).sum())
        frac_done = np.mean([(done_at[i][~downs[i]] > 0).mean()
                             for i in range(seeds)])
        assert frac_done > 0.99, f"Handel did not converge: {frac_done:.3f}"
        assert dropped == 0 and bc_dropped == 0 and clamped == 0
        assert evicted == 0   # queue never overflowed
        return {}

    rstats = (_route_stats(base, init, superstep, engine)
              if route_stats else {})
    return step, init, steps, check, proto, superstep, engine, rstats


def _fixed_cost_estimate(n, seeds, chunk, mode, horizon, inbox_cap,
                         box_split, eff_ss):
    """Two-point per-ms fixed-cost estimate for the bench JSON line.

    The superstep-K window removes (K-1)/K of the per-ms fixed cost
    (sort/scatter/slice/clear — core/network.step_kms) and none of the
    per-ms protocol work, so timing a short window at superstep=1 and
    at the effective K gives ``fixed ≈ (c1 - cK) * K / (K - 1)`` where
    c is wall time per simulated ms of the whole seed batch.  A 2-chunk
    calibration (no convergence assert — too short to converge) keeps
    the overhead to one extra compile; WTPU_FIXED_COST_EST=0 skips.

    Both legs are pinned to the VMAPPED DENSE scan engine regardless of
    what the measured run uses: the formula is only valid when the two
    legs differ solely in K.  The seed-folded batched engine cannot run
    superstep=1 (it is hard-wired to the fused window), and the
    fast-forward while-loop's wall time is dominated by skip/jump
    behavior rather than the sort/scatter fixed cost — letting the
    default env pick per leg would conflate the ~14% batched-vs-vmapped
    engine delta (BENCH_NOTES r4) or the quiet-window skip rate with
    the amortization being estimated, so both env knobs are forced off
    around the legs."""
    if eff_ss <= 1 or os.environ.get("WTPU_FIXED_COST_EST", "1") == "0":
        return {}
    from wittgenstein_tpu.utils.measure import timed_chunks
    prev = {name: os.environ.get(name)
            for name in ("WTPU_BENCH_BATCHED", "WTPU_FAST_FORWARD")}
    os.environ["WTPU_BENCH_BATCHED"] = "0"
    os.environ["WTPU_FAST_FORWARD"] = "0"
    try:
        cost_us = {}
        for ss in (1, eff_ss):
            step, init, _, _, _, _, _, _ = _handel_setup(
                n, seeds, 2 * chunk, chunk, mode, horizon, inbox_cap, ss,
                box_split=box_split)
            r = timed_chunks(step, init, 2, seeds, chunk,
                             lambda nets, ps: {}, reps=1)
            cost_us[ss] = 1e6 * seeds / r["value"]   # µs per simulated ms
    finally:
        for name, value in prev.items():
            if value is None:
                del os.environ[name]
            else:
                os.environ[name] = value
    c1, ck = cost_us[1], cost_us[eff_ss]
    fixed = max(0.0, (c1 - ck) * eff_ss / (eff_ss - 1))
    return {
        "fixed_cost_cal_us_per_ms": {"ss1": round(c1, 2),
                                     f"ss{eff_ss}": round(ck, 2)},
        "fixed_cost_est_us_per_ms": round(fixed, 2),
        "fixed_cost_frac_est": round(fixed / c1, 4) if c1 > 0 else 0.0,
    }


def bench_handel(n=2048, seeds=8, sim_ms=1000, chunk=200, mode="exact",
                 horizon=256, inbox_cap=12, reps=3, superstep=1,
                 box_split=1):
    """Timed Handel runs under the shared un-fakeable measurement
    protocol (`wittgenstein_tpu.utils.measure.timed_chunks` — in-window
    materialization, >= reps repetitions with median + min/max, and a
    synchronous cross-check rep; see its docstring and the round-4
    postmortem in BENCH_NOTES.md for why).

    Returns a result dict (rate + provenance), not a bare float.
    """
    from wittgenstein_tpu.utils.measure import timed_chunks
    step, init, steps, check, proto, eff_ss, engine, rstats = \
        _handel_setup(n, seeds, sim_ms, chunk, mode, horizon, inbox_cap,
                      superstep, box_split=box_split, route_stats=True)
    _check_trace_cap(steps * chunk)
    res = timed_chunks(step, init, steps, seeds, chunk, check, reps=reps)
    res["superstep"] = eff_ss
    res["engine"] = engine
    res.update(rstats)
    res.update(_fixed_cost_estimate(n, seeds, chunk, mode, horizon,
                                    inbox_cap, box_split, eff_ss))
    res.update(_ff_stats(step, steps, chunk))
    return _maybe_engine_metrics(
        res, proto, seeds, steps * chunk,
        fast_forward=os.environ.get("WTPU_FAST_FORWARD") == "1")


def bench_handel_microbatched(n=2048, total_seeds=256, seed_batch=16,
                              sim_ms=1000, chunk=200, mode="exact",
                              horizon=256, inbox_cap=12, superstep=1,
                              box_split=1):
    """The 256-seed path (RunMultipleTimes.java:41-87 at scale): the vmap
    batch is capped by single-chip memory (16 seeds at the headline
    config, BENCH_NOTES.md r3), so larger seed counts run as SEQUENTIAL
    microbatches of the same jitted program — deterministic, so exactly
    equivalent to one big batch, with only one microbatch's state
    resident at a time.

    Measurement: one timed window covering all microbatches, each
    materialized (convergence + drop asserts) inside the window; per-
    microbatch walls reported as spread.  Returns a result dict.
    """
    import time
    assert total_seeds % seed_batch == 0
    n_batches = total_seeds // seed_batch
    step, init, steps, check, proto, eff_ss, engine, rstats = \
        _handel_setup(n, seed_batch, sim_ms, chunk, mode, horizon,
                      inbox_cap, superstep, box_split=box_split,
                      route_stats=True)
    _check_trace_cap(steps * chunk)

    # compile + warm one chunk
    nets, ps = init(0)
    nets, ps = step(nets, ps)
    np.asarray(nets.time)

    walls = []
    t0_all = time.perf_counter()
    for b in range(n_batches):
        tb = time.perf_counter()
        nets, ps = init(b * seed_batch)
        for _ in range(steps):
            nets, ps = step(nets, ps)
        check(nets, ps)                     # materialize inside the window
        walls.append(time.perf_counter() - tb)
    wall = time.perf_counter() - t0_all
    # steps*chunk ms actually simulated per seed (sim_ms rounded up to a
    # whole number of chunks) — same accounting as measure.timed_chunks.
    agg = total_seeds * steps * chunk / wall
    out = {
        "value": round(agg, 1),
        "unit": "sim_ms/s",
        "total_seeds": total_seeds,
        "seed_batch": seed_batch,
        "microbatches": n_batches,
        "wall_total_s": round(wall, 1),
        "batch_wall_median_s": round(float(np.median(walls)), 2),
        "batch_wall_min_s": round(min(walls), 2),
        "batch_wall_max_s": round(max(walls), 2),
        "crosscheck": "per_batch_materialization",
        "superstep": eff_ss,
        "engine": engine,
        **rstats,
    }
    # All microbatches' chunks (warmup excluded by the tail slice);
    # skip_rate is then the average across the whole seed sweep.
    out.update(_ff_stats(step, steps * n_batches, chunk))
    # One microbatch's worth of engine metrics (runs are deterministic
    # per seed; the first batch is representative of the sweep).
    return _maybe_engine_metrics(
        out, proto, seed_batch, steps * chunk,
        fast_forward=os.environ.get("WTPU_FAST_FORWARD") == "1")


def bench_quiet(proto_name, n=256, seeds=4, sim_ms=1000, chunk=200,
                reps=3, superstep=2):
    """Quiet-heavy protocol bench (WTPU_BENCH_PROTO=pingpong|dfinity):
    the configs where fast-forwarding, not node width, is the lever.
    PingPong is delivery-driven after t == 0 (every in-flight-latency
    window skips); Dfinity at the reference round time (3000 ms paced by
    10 ms ticks) idles between consensus waves.  Same un-fakeable
    measurement protocol as the Handel headline; `n` sizes PingPong and
    is ignored by Dfinity (its node count is role-derived).

    With WTPU_FAST_FORWARD=1 the emitted dict carries `skipped_ms` /
    `jump_count` / `skip_rate` so the speedup is attributable."""
    from wittgenstein_tpu.core.network import (fast_forward_chunk,
                                               scan_chunk)
    from wittgenstein_tpu.utils.measure import timed_chunks
    fast_forward = os.environ.get("WTPU_FAST_FORWARD") == "1"
    # WTPU_LATENCY (the canonical spec-field spelling — from_env
    # refuses unknown names) / legacy WTPU_BENCH_LATENCY: the quiet
    # protocols honor the selection too, so the ledger row's
    # latency_model is always the model the run compiled.
    lat = _latency_env()
    lat_kw = {"network_latency_name": lat} if lat else {}
    if proto_name == "pingpong":
        from wittgenstein_tpu.models.pingpong import PingPong
        proto = PingPong(node_count=n, **lat_kw)
    elif proto_name == "dfinity":
        from wittgenstein_tpu.models.dfinity import Dfinity
        proto = Dfinity(**lat_kw)
    elif proto_name == "p2pflood":
        # Flood-shaped traffic: every live node fans out per ms — the
        # binning-bound extreme, the routing-megakernel A/B workload
        # (the latency override picks the floor-rich model that
        # licenses the K ladder; no-self-send floor = the model's).
        from wittgenstein_tpu.models.p2pflood import P2PFlood
        proto = P2PFlood(node_count=n, dead_node_count=n // 10,
                         peers_count=8, delay_before_resent=1,
                         delay_between_sends=1, **lat_kw)
    else:
        raise ValueError(f"unknown WTPU_BENCH_PROTO {proto_name!r}; "
                         "known: handel pingpong dfinity p2pflood")
    # Largest provable K under the requested bound: PingPong and Dfinity
    # both self-send (witness self-pong / committee addressing), so
    # their window caps at the universal K = 2.
    from wittgenstein_tpu.core.network import pick_superstep
    eff_ss = pick_superstep(
        proto, chunk, t0=0,
        max_k=32 if superstep == "auto" else int(superstep))
    if fast_forward:
        base = fast_forward_chunk(proto, chunk, seed_axis=True,
                                  superstep=eff_ss)
        step = _ff_step_wrapper(jax.jit(base))
    else:
        base = jax.vmap(scan_chunk(proto, chunk, superstep=eff_ss))
        step = jax.jit(base)
    steps = max(1, -(-sim_ms // chunk))
    _check_trace_cap(steps * chunk)

    def init(seed0=0):
        return jax.vmap(proto.init)(
            seed0 + jnp.arange(seeds, dtype=jnp.int32))

    def check(nets, ps):
        dropped = int(np.asarray(nets.dropped).sum())
        bc_dropped = int(np.asarray(nets.bc_dropped).sum())
        if proto_name == "pingpong":
            progress = int(np.asarray(ps.pongs).sum())
        elif proto_name == "p2pflood":
            progress = int((np.asarray(nets.nodes.done_at) > 0).sum())
        else:
            progress = int(np.asarray(ps.arena.height).max())
        assert progress > 0, f"{proto_name} made no progress"
        return {"progress": progress, "dropped": dropped,
                "bc_dropped": bc_dropped}

    res = timed_chunks(step, init, steps, seeds, chunk, check, reps=reps)
    res.update(_ff_stats(step, steps, chunk))
    res["node_count"] = proto.cfg.n
    res["superstep"] = eff_ss
    res["engine"] = "fast_forward" if fast_forward else "vmapped"
    res.update(_route_stats(base, init, eff_ss, res["engine"]))
    return _maybe_engine_metrics(res, proto, seeds, steps * chunk,
                                 fast_forward=fast_forward)


def _int_env(name, default):
    """One tolerant scalar-int env read: a malformed override must not
    crash the bench before it emits its metric line.  Delegates to the
    shared definition (`serve.spec.int_env`) so the knob parsing the
    one-config-path contract depends on cannot silently fork."""
    from wittgenstein_tpu.serve.spec import int_env
    return int_env(name, default, prefix="bench")


def main():
    # Persistent compile cache ($JAX_COMPILATION_CACHE_DIR, else the
    # fixed reports/jax_cache/): repeated runs and A/Bs stop paying full
    # recompiles.  The entry-count delta is the hit/miss signal.
    from wittgenstein_tpu.core.harness import (cache_entry_count,
                                               enable_persistent_cache)
    cache_dir = enable_persistent_cache()
    cache_before = cache_entry_count(cache_dir)
    # ONE config path (wittgenstein_tpu/serve/spec.py): the WTPU_* flag
    # soup is captured as a ScenarioSpec — the same object the request
    # plane and bench_suite use — and the bench reads its knobs back
    # OUT of the spec, so the ledger's config digest IS the spec digest
    # (no second source of truth).  Measurement-protocol knobs (reps,
    # microbatching, box_split) are not scenario config and stay env.
    from wittgenstein_tpu.serve.spec import ScenarioSpec
    spec = ScenarioSpec.from_env()
    # proto_sel stays the RAW env value: an unknown selection must
    # reach bench_quiet's loud refusal (before any ledger append),
    # never silently coerce to the Handel headline.
    proto_sel = os.environ.get("WTPU_BENCH_PROTO", "handel")
    n = spec.params.get("node_count", _int_env("WTPU_BENCH_NODES", 2048))
    seeds = len(spec.seeds)
    sim_ms = spec.sim_ms
    # The scan length per jitted call.  An explicit superstep K needs
    # chunk % K == 0 (the gate refuses instead of mislabeling the A/B),
    # so ladder scripts probing K > 8 override the default 200 — e.g.
    # 240 admits every K in {2, 4, 8, 16} while staying a multiple of
    # Handel's schedule lcm 20 (phase specialization stays on).
    chunk = spec.chunk_ms
    mode = spec.params.get("mode",
                           os.environ.get("WTPU_BENCH_MODE", "exact"))
    horizon = spec.params.get("horizon",
                              _int_env("WTPU_BENCH_HORIZON", 256))
    # inbox 12 measured drop-free at both the 2048-node headline config
    # and the 65536-node cardinal tier-2 config (BENCH_NOTES.md r3).
    inbox_cap = spec.params.get("inbox_cap",
                                _int_env("WTPU_BENCH_INBOX", 12))
    reps = _int_env("WTPU_BENCH_REPS", 3)
    # WTPU_SUPERSTEP=K runs the fused K-ms window engine
    # (core/network.step_kms, bit-identical — tests/test_superstep.py);
    # "auto" picks the largest K the latency floor proves.  The legacy
    # WTPU_BENCH_SUPERSTEP spelling still works; default stays the
    # universally-valid 2 (ScenarioSpec.from_env mirrors the rule).
    superstep = spec.superstep
    # Seed counts past the single-chip vmap ceiling run as sequential
    # microbatches (the 256-seed path, RunMultipleTimes.java:41-87).
    seed_batch = _int_env("WTPU_BENCH_SEED_BATCH", 16)
    box_split = _int_env("WTPU_BENCH_BOX_SPLIT", 1)
    if proto_sel != "handel":
        res = bench_quiet(proto_sel, n=n, seeds=seeds, sim_ms=sim_ms,
                          chunk=chunk, reps=reps, superstep=superstep)
        n = res.pop("node_count")
    elif seeds > seed_batch:
        res = bench_handel_microbatched(
            n=n, total_seeds=seeds, seed_batch=seed_batch,
            sim_ms=sim_ms, chunk=chunk, mode=mode, horizon=horizon,
            inbox_cap=inbox_cap, superstep=superstep,
            box_split=box_split)
    else:
        res = bench_handel(n=n, seeds=seeds, sim_ms=sim_ms,
                           chunk=chunk, mode=mode,
                           horizon=horizon, inbox_cap=inbox_cap,
                           reps=reps, superstep=superstep,
                           box_split=box_split)
    suffix = ""
    if mode != "exact" and proto_sel == "handel":
        suffix = f"_{mode}"
    if res.get("fast_forward"):
        suffix = f"_ff{suffix}"
    agg = res.pop("value")
    res.pop("unit", None)
    cache_new = cache_entry_count(cache_dir) - cache_before
    out = {
        "metric": f"{proto_sel}_{n}n_{seeds}seeds_agg_sim_ms_per_sec"
                  f"{suffix}",
        "value": agg,
        "unit": "sim_ms/s",
        "vs_baseline": round(agg / 10_000.0, 3),
        "platform": jax.default_backend(),
        "device": {"kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
        "compile_cache": ("off" if cache_dir is None else
                          "hit" if cache_new == 0 else "miss"),
        "compile_cache_new_entries": cache_new,
        **res,
    }
    _append_ledger(out, spec, n=n, seeds=seeds, mode=mode, chunk=chunk,
                   proto_sel=proto_sel)
    print(json.dumps(out))


def _append_ledger(out, spec, **extra):
    """One `RunManifest` provenance row per emitted metric line
    (`obs.ledger.append_from_spec`; ``WTPU_LEDGER=0`` skips).  The
    config digest is the `ScenarioSpec` digest — the one definition
    bench, bench_suite and serve share — and the engine label comes
    from the setup that CHOSE the dispatch (the bench fns put it in
    the line), never re-derived."""
    if os.environ.get("WTPU_LEDGER", "1") == "0":
        return
    from wittgenstein_tpu.obs import ledger
    path = ledger.append_from_spec(out, spec, **extra)
    if path:
        print(f"bench: ledger row appended -> {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
