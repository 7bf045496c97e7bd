"""Multi-config benchmark: the BASELINE.md tracked configs, one honest
JSON line each (VERDICT r3 next #7).

Configs (BASELINE.md "Tracked configs"):
  * PingPong 1k    — the README example (README.md:123-135 curve)
  * GSFSignature 4k
  * SanFermin 32k
  * Dfinity 10k validators (10 BPs + 10,000 attesters, rotating
    100-attester committees)
plus smoke stages: trace_smoke (PR 5), audit_smoke (PR 6), serve_smoke
(PR 7 — 2 coalesced requests through the in-process request plane),
chaos_smoke (PR 10), matrix_smoke (PR 12), tenancy_smoke (PR 13),
memo_smoke (PR 14 — snapshot-fork prefix sharing bit-identical to the
unmemoized run, prefix_chunks_saved == the fork plan's prediction) and
crash_smoke (PR 15 — one real SIGKILL of a subprocess campaign,
journal+checkpoint resume, report bit-identity asserted, plus the
/w/batch/health round trip over real HTTP), analysis_smoke (PR 16
— the full `--source` static-analysis pass as a subprocess, budgets
enforced, wall time under 60 s), spans_smoke (PR 18 — one
instrumented request with the host flight recorder ON: the lifecycle
span set asserted complete and ordered, the /w/batch/metrics
Prometheus endpoint round-tripped over real HTTP with monotone
counters across scrapes) and catalog_smoke (PR 20 — one request
through a catalog-attached scheduler: the cold build round-trips one
durable program-catalog row, the cost-model drift and registry gauges
land on a real-HTTP scrape, /w/batch/programs serves the report).

Measurement protocol: the shared `wittgenstein_tpu.utils.measure`
module (the same one `bench.py` uses — ONE implementation of the
un-fakeable protocol).  A config that faults or fails its convergence
assert emits an `"error"` line instead of killing the suite.

Every emitted line also appends a row to the bench-history ledger
(reports/bench_history.jsonl; --no-history or WTPU_HISTORY=0 skips),
keyed on (stage, config digest, backend, host fingerprint);
``--check-regressions`` gates the round against same-host baselines
with the median/MAD detector (wittgenstein_tpu/obs/regress.py) and
exits 1 on a regression.  tools/regress.py runs the same gate after
the fact.

Usage: python tools/bench_suite.py [config ...]   (default: all)
Output: one JSON line per config on stdout.
"""

import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax                                        # noqa: E402
import jax.numpy as jnp                           # noqa: E402
import numpy as np                                # noqa: E402

from wittgenstein_tpu.core.network import scan_chunk   # noqa: E402
from wittgenstein_tpu.utils.measure import timed_chunks  # noqa: E402


def _env_superstep():
    """THE suite's WTPU_SUPERSTEP parse — `run_config` (what the run
    requests) and `_stage_spec` (what the ledger digests) share this
    single definition, so the digested K can never drift from the K
    the run requests.  Returns "auto" or an int >= 1 (malformed -> 1,
    the suite's historical default)."""
    import os

    raw = os.environ.get("WTPU_SUPERSTEP", "1")
    if raw == "auto":
        return "auto"
    try:
        return max(1, int(raw))
    except ValueError:
        print(f"bench_suite: ignoring malformed WTPU_SUPERSTEP={raw!r}; "
              f"using 1", file=sys.stderr)
        return 1


def run_config(proto, seeds, sim_ms, chunk, check, reps=2, t0_mod=None,
               superstep=None):
    """Build the jitted step/init for one config and measure it.

    `superstep=None` honors the WTPU_SUPERSTEP override (int or "auto";
    default 1 keeps the tracked configs comparable with their history);
    the effective K — auto-picked and floor-gated like bench.py — is
    recorded in the JSON line."""
    from wittgenstein_tpu.core.network import pick_superstep
    if superstep is None:
        superstep = _env_superstep()
    if superstep == "auto" or superstep > 1:
        superstep = pick_superstep(
            proto, chunk, t0=0,
            max_k=32 if superstep == "auto" else superstep,
            lcm=getattr(proto, "schedule_lcm", None)
            if t0_mod is not None else None)
    sc = scan_chunk(proto, chunk, t0_mod=t0_mod, superstep=superstep)
    if seeds is None:
        step = jax.jit(sc)
        init_jit = jax.jit(proto.init)      # built once: keep the trace
        #                                     cache across measurement reps
        init = lambda: init_jit(jnp.asarray(0, jnp.int32))   # noqa: E731
    else:
        step = jax.jit(jax.vmap(sc))
        init_jit = jax.vmap(proto.init)
        init = lambda: init_jit(                             # noqa: E731
            jnp.arange(seeds, dtype=jnp.int32))
    steps = max(1, -(-sim_ms // chunk))
    out = timed_chunks(step, init, steps, seeds or 1, chunk, check,
                       reps=reps)
    out.update(sim_ms=steps * chunk, batch=seeds or 1,
               superstep=superstep, platform=jax.default_backend())
    # engine_metrics block (wittgenstein_tpu/obs; schema BENCH_NOTES.md):
    # an un-timed bit-identical instrumented pass — the timed reps above
    # stay on the uninstrumented engine.  WTPU_METRICS=0 skips (checked
    # inside, one shared guard).
    from bench import _maybe_engine_metrics
    _maybe_engine_metrics(out, proto, seeds or 1, steps * chunk)
    return out


def bench_pingpong():
    """README example: 1000 nodes, ByDistanceWJitter; every pong is back
    at the witness by 800 ms (README.md:123-135: 1000 at 700 ms)."""
    from wittgenstein_tpu.models.pingpong import PingPong
    proto = PingPong(node_count=1000)
    seeds = 4

    def check(nets, ps):
        pongs = np.asarray(ps.pongs)
        dropped = int(np.asarray(nets.dropped).sum())
        assert dropped == 0, f"dropped={dropped}"
        assert (pongs >= 1000).all(), f"pongs={pongs.tolist()}"
        return {"pongs_min": int(pongs.min())}

    return run_config(proto, seeds, 800, 100, check)


def bench_gsf():
    from wittgenstein_tpu.models.gsf import GSFSignature
    proto = GSFSignature(node_count=4096)      # threshold 0.99N
    seeds = 4

    def check(nets, ps):
        done_at = np.asarray(nets.nodes.done_at)
        dropped = int(np.asarray(nets.dropped).sum())
        clamped = int(np.asarray(nets.clamped).sum())
        frac = (done_at > 0).mean()
        assert dropped == 0 and clamped == 0, (dropped, clamped)
        assert frac > 0.99, f"frac_done={frac:.3f}"
        return {"frac_done": round(float(frac), 4)}

    return run_config(proto, seeds, 2500, 250, check)


def bench_sanfermin():
    """32k nodes.  The r4 attempts drowned in request fan-in (inbox 8
    dropped 61,684, 16 still 20,005, and 32's 8.6 GB ring hit
    RESOURCE_EXHAUSTED): the index-order candidate walk aims every
    block's stragglers at the sibling block's first ids.  The rotated
    pick order (models/sanfermin._pick_offset) makes every pick index a
    requester<->candidate bijection — measured ZERO drops at 4096 nodes
    with inbox 12 (r5) — so 16 now carries margin.  box_split=2 (537 MB
    sub-planes) was sized for a ~1 GB buffer limit of an earlier remote
    runtime that a v5e does not have (a 1.5 GB buffer works, PR 21 chip
    run); the layout is bit-identical either way."""
    import dataclasses

    from wittgenstein_tpu.models.sanfermin import SanFermin
    proto = SanFermin(node_count=32768, inbox_cap=16)
    proto.cfg = dataclasses.replace(proto.cfg, box_split=2)
    seeds = None                                # single seed, unbatched

    def check(nets, ps):
        done_at = np.asarray(nets.nodes.done_at)
        dropped = int(np.asarray(nets.dropped).sum())
        finished = done_at[done_at > 0]
        stranded = 1.0 - finished.size / done_at.size
        assert dropped == 0, f"dropped={dropped}"
        # The reference itself strands candidate-exhausted nodes
        # (SanFerminSignature.java:330-340); small tail allowed.
        assert stranded <= 0.02, f"stranded={stranded:.1%}"
        return {"stranded_pct": round(100 * stranded, 2),
                "done_mean_ms": round(float(finished.mean()), 1)}

    return run_config(proto, seeds, 6000, 500, check)


def bench_dfinity():
    """10k validators: 10 block producers + 10,000 attesters in rotating
    100-attester committees, ~3 s per height (Dfinity.java:467-481
    pacing), 120 simulated seconds."""
    from wittgenstein_tpu.models.dfinity import Dfinity
    proto = Dfinity(block_producers_count=10, attesters_count=10_000,
                    attesters_per_round=100, block_capacity=512)
    seeds = None

    def check(nets, ps):
        heights = np.asarray(ps.arena.height)[np.asarray(ps.head)]
        dropped = int(np.asarray(nets.dropped).sum())
        arena_dropped = int(np.asarray(ps.arena.dropped))
        assert dropped == 0 and arena_dropped == 0, (dropped, arena_dropped)
        # At a mid-round snapshot one block can legitimately be in
        # flight: heads may skew by 1, never more.
        assert heights.max() - heights.min() <= 1, "nodes disagree"
        assert heights.max() >= 30, f"height={heights.max()} after 120 s"
        return {"height": int(heights.max())}

    return run_config(proto, seeds, 120_000, 2000, check)


def bench_trace_smoke():
    """Flight-recorder smoke stage (PR 5): a tiny PingPong capture at a
    deliberately small ring, full decode + Perfetto round-trip — the
    whole trace path (tap -> ring -> TraceFrame -> exporter) exercised
    end to end in seconds, so a decoder or exporter regression surfaces
    in the suite instead of during a debugging session.  The capacity
    is sized to the span (no truncation expected; `dropped` is asserted
    and reported either way)."""
    from wittgenstein_tpu.core.harness import capture_trace
    from wittgenstein_tpu.models.pingpong import PingPong
    from wittgenstein_tpu.obs import (TraceSpec, trace_block,
                                      trace_to_perfetto)

    proto = PingPong(node_count=64)
    spec = TraceSpec(capacity=1024)
    frame, net, ps = capture_trace(proto, 120, spec)
    blk = trace_block(frame)
    assert blk["events"] > 0, "trace smoke recorded nothing"
    assert not blk["truncated"], blk
    # decode round-trip: formatted listing + per-kind counts agree
    assert len(frame.rows()) == blk["events"]
    perfetto = trace_to_perfetto(frame)     # in-memory render
    n_slices = sum(1 for e in perfetto["traceEvents"]
                   if e.get("ph") == "X")
    assert n_slices == blk["events"], (n_slices, blk["events"])
    json.dumps(blk)                         # one-line-JSON embeddable
    return {"metric": "trace_smoke_events", "value": blk["events"],
            "unit": "events", "perfetto_slices": n_slices, **blk,
            "platform": jax.default_backend()}


def bench_audit_smoke():
    """Invariant-audit smoke stage (PR 6): a tiny PingPong run with the
    compiled conservation-law monitors ON, zero violations asserted,
    and one `RunManifest` ledger row round-tripped — the whole audit
    path (tap -> AuditCarry -> AuditReport -> ledger) exercised end to
    end in seconds, so a monitor or ledger regression surfaces in the
    suite instead of during an incident."""
    import dataclasses
    import os
    import tempfile

    from wittgenstein_tpu.models.pingpong import PingPong
    from wittgenstein_tpu.obs import ledger
    from wittgenstein_tpu.obs.audit import AuditSpec, monitored_invariants
    from wittgenstein_tpu.obs.audit_report import audit_block, audit_variant

    proto = PingPong(node_count=64)
    spec = AuditSpec(mode="first")
    report, _ = audit_variant(proto, 120, {"superstep": 1}, spec)
    assert report.clean, report.format()
    blk = audit_block(report)
    assert blk["clean"] and blk["total"] == 0, blk
    # the verdict claims exactly the invariants this build compiled
    assert set(blk["violations"]) == \
        set(monitored_invariants(spec, proto.cfg))
    json.dumps(blk)                         # one-line-JSON embeddable

    # ledger round trip against an ISOLATED file: the shared
    # reports/ledger/ledger.jsonl is append-only and written by any
    # concurrent bench process, so a rows[-1] equality there would
    # race (and slow down with accumulated history); the real ledger
    # still gets this stage's row via the suite's _append_ledger
    mani = ledger.manifest_from_bench(
        {"metric": "audit_smoke", "sim_ms": 120, "superstep": 1,
         "audit": blk},
        config={"proto": "pingpong", "nodes": 64, "ms": 120,
                "stage": "audit_smoke", "engine": "vmapped"},
        label="audit_smoke")
    fd, tmp = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    try:
        assert ledger.append(mani, tmp) == tmp, "ledger append failed"
        rows = ledger.read_all(tmp)
        assert len(rows) == 1, rows
        assert dataclasses.asdict(rows[0]) == dataclasses.asdict(mani), \
            "ledger round-trip mismatch"
    finally:
        os.unlink(tmp)
    return {"metric": "audit_smoke_violations", "value": report.total,
            "unit": "violations", "audit": blk,
            "ledger_round_trip": "ok",
            "platform": jax.default_backend()}


def bench_serve_smoke():
    """Request-plane smoke stage (PR 7): an in-process `serve.Service`,
    2 coalesced requests (one compile key, different seeds) through
    submit -> drain -> result, artifacts and per-request ledger rows
    asserted — the whole plane (spec validation -> registry -> the
    coalescing scheduler -> artifacts -> ledger) exercised end to end
    in seconds, so a request-plane regression surfaces in the suite
    instead of during a service incident.  The ledger round-trips
    against an ISOLATED temp file (the audit_smoke convention — the
    shared ledger is append-only and concurrently written)."""
    import dataclasses
    import os
    import tempfile

    import wittgenstein_tpu.models  # noqa: F401 — fills the registry
    from wittgenstein_tpu.obs import ledger
    from wittgenstein_tpu.serve import ScenarioSpec, Scheduler, Service

    spec = ScenarioSpec(protocol="PingPong", params={"node_count": 64},
                        seeds=(0,), sim_ms=120, chunk_ms=120,
                        obs=("metrics", "audit"))
    fd, tmp = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    try:
        svc = Service(scheduler=Scheduler(ledger_path=tmp), auto=False)
        a = svc.submit(spec.to_json())
        b = svc.submit(dataclasses.replace(spec, seeds=(1,)).to_json())
        assert a["compile_key"] == b["compile_key"], "must coalesce"
        svc.run_pending()
        ra, rb = svc.result(a["id"]), svc.result(b["id"])
        assert ra["status"] == "done" and rb["status"] == "done"
        assert ra["audit"]["clean"] and rb["audit"]["clean"]
        assert ra["engine_metrics"]["totals"]["msg_sent"] > 0
        assert ra["summary"]["done_count"] > 0
        json.dumps(ra), json.dumps(rb)      # one-line-JSON embeddable
        rows = ledger.read_all(tmp)
        assert len(rows) == 2, rows
        assert all(r.audit_clean for r in rows)
        assert rows[0].config_digest == spec.digest()
        reg = svc.registry_stats()
        assert reg["misses"] >= 1
        return {"metric": "serve_smoke_requests", "value": 2,
                "unit": "requests", "registry": reg,
                "audit_clean": True, "ledger_rows": len(rows),
                "platform": jax.default_backend()}
    finally:
        os.unlink(tmp)


#: the chaos_smoke stage's schedule — module-level so the stage and its
#: `_stage_spec` digest entry can never drift apart (transitions all
#: even: the K=2 cross-variant pin needs window-aligned times)
CHAOS_SMOKE_SCHEDULE = {
    "churn": [[3, 20, 60], [5, 40, 100]],
    "partitions": [[30, 90, 1, 0, 32]],
    "loss": [[0, 120, 250, 0, 64, 0, 64]],
}


def bench_chaos_smoke():
    """Chaos-plane smoke stage (PR 10): a tiny PingPong run under a
    churn + mid-run-partition + message-loss schedule — cross-variant
    bit-identity (dense vs superstep-2), a clean audit verdict over the
    faulted trajectory, the `node_down`/`node_up` flight-recorder
    kinds, a real impact vs the fault-free baseline, and one
    `RunManifest` ledger row round-tripped (isolated temp file, the
    audit_smoke convention) — the whole chaos path (FaultSchedule ->
    ChaosProtocol -> engine hooks -> obs planes -> ledger) exercised
    end to end in seconds."""
    import dataclasses
    import os
    import tempfile

    import numpy as np

    from wittgenstein_tpu.chaos import ChaosProtocol, FaultSchedule
    from wittgenstein_tpu.models.pingpong import PingPong
    from wittgenstein_tpu.obs import ledger
    from wittgenstein_tpu.obs.audit import AuditSpec
    from wittgenstein_tpu.obs.audit_report import audit_block, audit_variant
    from wittgenstein_tpu.obs.diff import first_divergence
    from wittgenstein_tpu.obs.trace import TraceSpec, scan_chunk_trace
    from wittgenstein_tpu.obs.decode import TraceFrame

    proto = PingPong(node_count=64)
    sched = FaultSchedule.from_json(CHAOS_SMOKE_SCHEDULE).validate(
        n=64, sim_ms=120)
    cp = ChaosProtocol(proto, sched)

    # cross-variant bit-identity under faults (the chaos contract)
    div = first_divergence(cp, {"superstep": 1}, {"superstep": 2}, 120)
    assert div is None, f"chaos cross-variant divergence:\n{div.format()}"

    # clean audit verdict over the FAULTED trajectory + impact
    report, (nets, _) = audit_variant(cp, 120, {"superstep": 1},
                                      AuditSpec())
    assert report.clean, report.format()
    blk = audit_block(report)
    _, (nets0, _) = audit_variant(proto, 120, {"superstep": 1},
                                  AuditSpec())
    lost = (int(np.asarray(nets0.nodes.msg_received).sum()) -
            int(np.asarray(nets.nodes.msg_received).sum()))
    assert lost > 0, "the schedule had no observable impact"

    # churn drives the node_down/node_up trace kinds at their exact ms
    tspec = TraceSpec(capacity=4096)
    _, _, tc = jax.jit(scan_chunk_trace(cp, 120, tspec))(*cp.init(0))
    counts = TraceFrame.from_carry(tspec, tc).counts()
    assert counts.get("node_down") == 2 and counts.get("node_up") == 2, \
        counts

    # ledger row round trip (isolated file; the real ledger still gets
    # this stage's row via the suite's _append_ledger)
    res = {"metric": "chaos_smoke_lost_msgs", "value": lost,
           "unit": "messages", "sim_ms": 120, "superstep": 1,
           "audit": blk, "schedule": sched.counts(),
           "trace_counts": {k: counts[k]
                            for k in ("node_down", "node_up")},
           "platform": jax.default_backend()}
    spec = _stage_spec("chaos_smoke")
    fd, tmp = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    try:
        mani = ledger.manifest_from_spec(res, spec, label="chaos_smoke")
        assert ledger.append(mani, tmp) == tmp, "ledger append failed"
        rows = ledger.read_all(tmp)
        assert len(rows) == 1 and rows[0].audit_clean, rows
        assert rows[0].config_digest == spec.digest()
        assert dataclasses.asdict(rows[0]) == dataclasses.asdict(mani), \
            "ledger round-trip mismatch"
    finally:
        os.unlink(tmp)
    res["ledger_round_trip"] = "ok"
    json.dumps(res)                         # one-line-JSON embeddable
    return res


#: the matrix_smoke stage's grid — module-level so the stage and any
#: consumer of its digest can never drift apart (the chaos_smoke
#: convention); 2 x 2 x 2: seeds are data, the latency axis splits the
#: compile key, the span axis is data again -> exactly 2 distinct keys
MATRIX_SMOKE_GRID = {
    "name": "matrix_smoke",
    "base": {"protocol": "PingPong", "params": {"node_count": 64},
             "seeds": [0], "sim_ms": 120, "chunk_ms": 120,
             "obs": ["metrics", "audit"]},
    "axes": [
        {"name": "seed", "field": "seeds", "values": [[0], [1]]},
        {"name": "lat", "field": "latency_model",
         "values": [None, "NetworkFixedLatency(30)"]},
        {"name": "span", "field": "sim_ms", "values": [120, 240]},
    ],
}


def bench_matrix_smoke():
    """Sweep-grid smoke stage (PR 12): a tiny 2 x 2 x 2 grid through the
    in-process `Service`'s /w/matrix trio (submit -> run -> report) —
    planned compiles == distinct compile keys == actual program builds
    asserted, the `MatrixReport` artifact round-tripped through its
    JSON form, and every per-cell `RunManifest` ledger row carrying the
    grid digest (isolated temp file, the audit_smoke convention) — the
    whole matrix path (SweepGrid -> planner -> scheduler coalescing ->
    report -> ledger) exercised end to end in seconds."""
    import os
    import tempfile

    import wittgenstein_tpu.models  # noqa: F401 — fills the registry
    from wittgenstein_tpu.matrix import MatrixReport, SweepGrid
    from wittgenstein_tpu.obs import ledger
    from wittgenstein_tpu.serve import Scheduler, Service

    grid = SweepGrid.from_json(MATRIX_SMOKE_GRID)
    fd, tmp = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    try:
        svc = Service(scheduler=Scheduler(ledger_path=tmp), auto=False)
        sub = svc.matrix_submit(MATRIX_SMOKE_GRID)
        assert sub["cells"] == 8 and sub["planned_compiles"] == 2, sub
        assert sub["grid_digest"] == grid.grid_digest()
        st = svc.matrix_run(sub["id"])
        assert st["status"] == "done", st
        rep = svc.matrix_report(sub["id"])
        assert rep["cells_done"] == 8 and rep["cells_error"] == 0, rep
        assert rep["audit_violations"] == 0
        # the compile-key-minimal pin: builds == distinct keys x planes
        assert rep["planned_compiles"] == rep["distinct_compile_keys"] \
            == 2, rep
        assert rep["program_builds"] == rep["expected_builds"] == 4, rep
        # report artifact round-trips through its JSON form exactly
        # (the "status" key is the poll envelope, not the artifact)
        art = {k: v for k, v in rep.items() if k != "status"}
        again = MatrixReport.from_json(json.loads(json.dumps(art)))
        assert again.to_json() == art, "report round-trip mismatch"
        assert again.grid_digest == grid.grid_digest()
        # per-cell ledger rows carry the grid digest + axis labels
        rows = ledger.read_all(tmp)
        assert len(rows) == 8, rows
        assert all(r.extra.get("grid_digest") == grid.grid_digest()
                   for r in rows), rows
        assert all(r.run.startswith("matrix:") for r in rows)
        assert all(r.audit_clean for r in rows)
        return {"metric": "matrix_smoke_cells", "value": 8,
                "unit": "cells", "grid_digest": grid.grid_digest(),
                "planned_compiles": rep["planned_compiles"],
                "program_builds": rep["program_builds"],
                "wall_s": rep["wall_s"], "ledger_rows": len(rows),
                "platform": jax.default_backend()}
    finally:
        os.unlink(tmp)


def bench_tenancy_smoke():
    """Tenancy-plane smoke stage (PR 13): the multi-tenant scheduler
    end to end in seconds — deficit-round-robin FAIRNESS (an
    interactive tenant's request lands before a campaign backlog
    finishes: no tenant starved), chunk-boundary preemption with the
    preempted request still completing bit-consistently (audit-clean
    artifacts over the whole span), and the admission-control 429
    ROUND TRIP over real HTTP (over-budget submit -> 429 +
    Retry-After + retry_after_s body; the worker survives, a drain
    frees the queue, the retry lands 200)."""
    import dataclasses
    import threading
    import urllib.error
    import urllib.request

    import wittgenstein_tpu.models  # noqa: F401 — fills the registry
    from wittgenstein_tpu.serve import ScenarioSpec, Scheduler
    from wittgenstein_tpu.server.http import make_server

    spec = ScenarioSpec(protocol="PingPong", params={"node_count": 64},
                        seeds=(0,), sim_ms=120, chunk_ms=40,
                        obs=("metrics", "audit"), tenant="campaign")
    # --- fairness + preemption (in-process, manual drain)
    sched = Scheduler(tenants={"campaign": {"weight": 1},
                               "interactive": {"weight": 4}},
                      quantum_chunks=1, ledger_path=None)
    camp = [sched.submit(dataclasses.replace(spec, seeds=(s,)))
            for s in range(3)]
    inter = sched.submit(dataclasses.replace(
        spec, params={"node_count": 32}, tenant="interactive",
        deadline_ms=60_000))
    sched.run_pending()
    reqs = {r: sched.request(r) for r in camp + [inter]}
    assert all(q.status == "done" for q in reqs.values()), \
        {r: q.error for r, q in reqs.items()}
    assert all(q.artifacts["audit"]["clean"] for q in reqs.values())
    # no starvation, and fairness with teeth: the interactive request
    # finished BEFORE the campaign backlog's last request
    assert reqs[inter].finished < max(reqs[r].finished for r in camp)
    assert sched.resilience["preemptions"] >= 1, sched.resilience
    ten = sched.tenancy_stats()
    assert ten["tenants"]["interactive"]["done"] == 1
    assert ten["tenants"]["campaign"]["done"] == 3

    # --- 429 round trip over HTTP (bounded queue, manual drain)
    httpd = make_server(port=0, batch_auto=False, scheduler=Scheduler(
        tenants={"campaign": {"max_queued": 1, "retry_after_s": 0.25}}))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{port}"

    def post(path, body=None):
        req = urllib.request.Request(
            f"{base}{path}", method="POST",
            data=json.dumps(body or {}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, json.loads(resp.read()), resp.headers

    try:
        st, _, _ = post("/w/batch/submit", spec.to_json())
        assert st == 200
        try:
            post("/w/batch/submit",
                 dataclasses.replace(spec, seeds=(1,)).to_json())
            raise AssertionError("over-budget submit was not refused")
        except urllib.error.HTTPError as e:
            assert e.code == 429, e.code
            body = json.loads(e.read())
            assert body["retry_after_s"] >= 0.25, body
            assert "retry after" in body["error"], body
            assert int(e.headers["Retry-After"]) >= 1, dict(e.headers)
        # the worker never crashed: a drain frees the queue and the
        # retried submission lands
        st, _, _ = post("/w/batch/run")
        assert st == 200
        st, sub, _ = post("/w/batch/submit",
                          dataclasses.replace(spec, seeds=(1,)).to_json())
        assert st == 200, sub
        post("/w/batch/run")
        with urllib.request.urlopen(f"{base}/w/batch/tenancy",
                                    timeout=10) as resp:
            ten_http = json.loads(resp.read())
        assert ten_http["rejected"] == 1, ten_http
        assert ten_http["tenants"]["campaign"]["done"] == 2, ten_http
    finally:
        httpd.shutdown()
        httpd.server_close()
    return {"metric": "tenancy_smoke_requests", "value": 6,
            "unit": "requests", "preemptions":
            sched.resilience["preemptions"],
            "rejections_429": 1, "fairness": "no tenant starved",
            "platform": jax.default_backend()}


#: the memo_smoke stage's grid — module-level like MATRIX_SMOKE_GRID
#: (a consumer of its digest can never drift from the stage): a
#: chaos-axis sweep whose clean/loss cells share a 3-chunk honest
#: prefix per seed -> 2 fork groups, predicted prefix_chunks_saved =
#: 2 groups x 1 extra cell x 3 chunks = 6
MEMO_SMOKE_GRID = {
    "name": "memo_smoke",
    "base": {"protocol": "PingPong", "params": {"node_count": 64},
             "latency_model": "NetworkFixedLatency(10)",
             "seeds": [0], "sim_ms": 240, "chunk_ms": 40,
             "obs": ["metrics", "audit"]},
    "axes": [
        {"name": "seed", "field": "seeds", "values": [[0], [1]]},
        {"name": "chaos", "field": "fault_schedule",
         "values": [None, {"loss": [[120, 240, 400, 0, 64, 0, 64]]}],
         "labels": ["clean", "loss"]},
    ],
}

#: report keys that honestly differ between a memoized and an
#: unmemoized run of the SAME grid (wall clock, measured builds,
#: scheduler counters, the memo/fork provenance itself) — everything
#: else must be bit-identical, which is the stage's acceptance pin
MEMO_VOLATILE_KEYS = ("wall_s", "program_builds", "registry",
                      "resilience", "resume", "memo")


def _memo_norm_report(rep: dict) -> dict:
    import copy
    d = copy.deepcopy(rep)
    for k in MEMO_VOLATILE_KEYS:
        d.pop(k, None)
    for row in d["cells"]:
        row.pop("forked_from", None)
    return d


def bench_memo_smoke():
    """Memoized-supersteps smoke stage (PR 14): a small chaos-axis
    grid whose cells share an honest prefix runs twice — once plain,
    once with `run_grid(memo=True)` — and the stage asserts the memo
    contract end to end in seconds: `prefix_chunks_saved` > 0 AND
    equal to the fork plan's prediction, every forked cell's final
    pytree bit-identical to the unmemoized run's, the two
    `MatrixReport`s bit-identical outside the honestly-run-local
    keys (MEMO_VOLATILE_KEYS), and forked ledger rows carrying
    `forked_from` provenance."""
    import os
    import tempfile

    import numpy as np

    import wittgenstein_tpu.models  # noqa: F401 — fills the registry
    from wittgenstein_tpu.matrix import SweepGrid, plan, run_grid
    from wittgenstein_tpu.memo import plan_prefixes
    from wittgenstein_tpu.obs import ledger
    from wittgenstein_tpu.serve import Scheduler

    grid = SweepGrid.from_json(MEMO_SMOKE_GRID)
    mplan = plan(grid)
    predicted = plan_prefixes(mplan).predicted_chunks_saved
    assert predicted == 6, predicted
    with tempfile.TemporaryDirectory() as tmp:
        ref = run_grid(grid, Scheduler(
            ledger_path=os.path.join(tmp, "ref.jsonl")), plan_=mplan)
        mem = run_grid(grid, Scheduler(
            ledger_path=os.path.join(tmp, "memo.jsonl")), plan_=mplan,
            memo=True)
        blk = mem.report.data["memo"]
        assert blk["prefix_chunks_saved"] == predicted > 0, blk
        assert blk["forked_cells"] == 4 and blk["fork_vetoed"] == 0, blk
        assert _memo_norm_report(mem.report.to_json()) == \
            _memo_norm_report(ref.report.to_json()), \
            "memoized report differs from the unmemoized run"
        for cid, st in mem.states.items():
            for a, b in zip(jax.tree.leaves(st),
                            jax.tree.leaves(ref.states[cid])):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b),
                                              err_msg=cid)
        rows = ledger.read_all(os.path.join(tmp, "memo.jsonl"))
        forked = [r for r in rows
                  if (r.extra or {}).get("forked_from")]
        assert len(forked) == 4, [r.run for r in rows]
        assert all(r.extra["forked_from"]["fork_ms"] == 120
                   for r in forked)
    return {"metric": "memo_smoke_prefix_chunks_saved",
            "value": blk["prefix_chunks_saved"], "unit": "chunks",
            "memo": blk, "grid_digest": grid.grid_digest(),
            "cells": len(mplan.cells),
            "platform": jax.default_backend()}


def bench_crash_smoke():
    """Crash-safety smoke stage (PR 15): the kill-anywhere harness at
    minimum scale — the tiny crash campaign (tools/crash_test.py
    CRASH_GRID) runs uninterrupted once, then runs in a SUBPROCESS
    with journal + checkpoints + ledger ON, takes one real SIGKILL at
    a seeded offset, resumes to completion, and the final
    `MatrixReport` is asserted BIT-IDENTICAL to the uninterrupted
    run's (normalized over the honestly run-local keys).  Plus the
    health-endpoint round trip: `/w/batch/health` answers with the
    journal/quarantine/watchdog block over real HTTP."""
    import tempfile
    import urllib.request

    from tools.crash_test import run_crash_test
    from wittgenstein_tpu.server.http import make_server

    with tempfile.TemporaryDirectory() as tmp:
        res = run_crash_test(tmp, kills=1, seed=0)
    assert res["ok"], f"kill+resume report diverged: {res}"

    # /w/batch/health over real HTTP (the observability satellite)
    httpd = make_server(port=0, batch_auto=False)
    port = httpd.server_address[1]
    import threading
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/w/batch/health",
                timeout=10) as resp:
            health = json.loads(resp.read())
        for key in ("uptime_s", "queued_by_tenant", "journal_lag",
                    "quarantined", "watchdog_trips",
                    "chunk_wall_ema_s"):
            assert key in health, (key, health)
    finally:
        httpd.shutdown()
        httpd.server_close()
    return {"metric": "crash_smoke_bit_identical",
            "value": int(res["ok"]), "unit": "bool",
            "kills_landed": res["kills_landed"],
            "kills_missed": res["kills_missed"],
            "resume": res["resume"], "cells": res["cells"],
            "grid_digest": res["grid_digest"],
            "health_keys": sorted(health),
            "platform": jax.default_backend()}


def bench_analysis_smoke():
    """Host-plane static-analysis smoke stage (ISSUE 16): the full
    ``--source`` pass (determinism + host_locks/durability/digest/
    except against the checked-in budgets) as a SUBPROCESS — the same
    invocation CI and pre-commit hooks use, so a budget regression or
    a rule crash fails this stage, not just the test suite.  The
    metric is the scan's wall time (BENCH_NOTES.md pins it well under
    the 60 s smoke bound)."""
    import os
    import subprocess
    import tempfile
    import time

    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "report.json")
        t0 = time.monotonic()
        proc = subprocess.run(
            [sys.executable, "-m", "wittgenstein_tpu.analysis",
             "--source", "--json", out],
            cwd=str(REPO), capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        wall = time.monotonic() - t0
        assert proc.returncode == 0, \
            f"--source analysis failed:\n{proc.stdout}{proc.stderr}"
        with open(out) as fh:
            payload = json.load(fh)
    assert payload["ok"], payload
    assert wall < 60.0, f"source scan took {wall:.1f}s (budget 60s)"
    return {"metric": "analysis_smoke_wall_s",
            "value": round(wall, 2), "unit": "s",
            "schema": payload["schema"], "rules": payload["rules"],
            "n_findings": len(payload["findings"]),
            "platform": "cpu"}


def bench_fleet_smoke():
    """Fleet smoke stage (PR 17): two REAL worker subprocesses over
    one shared fleet directory complete a four-request mix submitted
    through the `FleetService` front tier.  One spec is pre-completed
    in-process first, so the stage asserts BOTH fleet mechanisms: the
    cross-worker ledger-dedup join (the duplicate settles without
    running, `deduped >= 1`) and lease-partitioned completion of the
    rest (every request `done`, aggregate throughput reported).  The
    workers' published stats snapshots are the measurement source —
    the same files `run_grid(workers=N)` aggregates.  The workers run
    on the CPU (``JAX_PLATFORMS=cpu``): the stage tests lease and queue
    semantics, not the device, and on a TPU host the chip belongs to
    this process."""
    import os
    import tempfile
    import time

    import wittgenstein_tpu.models  # noqa: F401 — fills the registry

    from wittgenstein_tpu.serve import FleetService
    from wittgenstein_tpu.serve.fleet import (FleetWorker,
                                              aggregate_worker_stats,
                                              spawn_workers)
    from wittgenstein_tpu.serve.spec import ScenarioSpec

    mk = lambda seed: ScenarioSpec(          # noqa: E731
        protocol="PingPong", params={"node_count": 64}, seeds=(seed,),
        sim_ms=120, chunk_ms=40, obs=("metrics", "audit"))
    with tempfile.TemporaryDirectory() as tmp:
        svc = FleetService(tmp)
        # pre-seed the shared ledger: one spec completed in-process
        # (an in-process FleetWorker — same code path, no subprocess;
        # step() alone publishes no stats snapshot, so the aggregate
        # below is the subprocess workers' alone)
        seed_worker = FleetWorker(tmp, "seed0")
        svc.submit(mk(0).to_json())
        for _ in range(60):
            seed_worker.step()
            if svc.journal.lag() == 0:
                break
        assert svc.journal.lag() == 0, "pre-seed request never settled"
        # the mix: the SAME spec again (dedup target) + three fresh
        rids = [svc.submit(mk(s).to_json())["id"] for s in
                (0, 1, 2, 3)]
        t0 = time.perf_counter()
        procs = spawn_workers(tmp, 2, idle_exit_s=2.0, max_wall_s=300.0,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"))
        deadline = time.time() + 300.0
        for p in procs:
            while p.poll() is None and time.time() < deadline:
                time.sleep(0.2)
        wall = time.perf_counter() - t0
        assert all(p.poll() is not None for p in procs), \
            "fleet workers did not idle-exit (wedged?)"
        statuses = {rid: svc.status(rid)["status"] for rid in rids}
        agg = aggregate_worker_stats(tmp)
        health = svc.health()
    assert all(s == "done" for s in statuses.values()), statuses
    c = agg["counters"]
    assert c.get("deduped", 0) >= 1, \
        f"ledger dedup never fired: {c}"
    assert c.get("processed", 0) >= 3, \
        f"subprocess workers processed too little: {c}"
    assert health["journal_lag"] == 0, health
    return {"metric": "fleet_smoke_requests", "value": len(rids),
            "unit": "requests", "wall_s": round(wall, 2),
            "throughput_rps": round(len(rids) / wall, 3),
            "workers": 2, "deduped": c.get("deduped", 0),
            "claimed": c.get("claimed", 0),
            "processed": c.get("processed", 0),
            "program_builds": agg["registry"].get("misses", 0),
            "platform": jax.default_backend()}


def bench_spans_smoke():
    """Host-plane observability smoke stage (PR 18): one instrumented
    request through the serve scheduler with the flight recorder ON,
    asserting the whole lifecycle span set (submit -> queue_wait ->
    compile -> launch -> chunk -> settle) is present and ordered, and
    the `/w/batch/metrics` Prometheus endpoint round-trips over REAL
    HTTP — two scrapes bracket the run, both parse, and every counter
    and histogram series is monotone across them."""
    import threading
    import time
    import urllib.request

    import wittgenstein_tpu.models  # noqa: F401 — fills the registry
    from wittgenstein_tpu.obs.metrics import parse_exposition
    from wittgenstein_tpu.serve import ScenarioSpec, Scheduler
    from wittgenstein_tpu.serve.instrument import (LIFECYCLE,
                                                   Instrumentation)
    from wittgenstein_tpu.server.http import make_server

    ins = Instrumentation(worker="smoke")
    sch = Scheduler(quantum_chunks=2, instrument=ins)
    spec = ScenarioSpec(protocol="PingPong", params={"node_count": 64},
                        seeds=(0,), sim_ms=120, chunk_ms=40,
                        obs=("metrics",))
    httpd = make_server(port=0, batch_auto=False, scheduler=sch)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{port}"

    def scrape():
        with urllib.request.urlopen(f"{base}/w/batch/metrics",
                                    timeout=10) as resp:
            ctype = resp.headers.get("Content-Type", "")
            assert ctype.startswith("text/plain"), ctype
            return parse_exposition(resp.read().decode())

    t0 = time.perf_counter()
    try:
        m0 = scrape()
        rid = sch.submit(spec)
        sch.run_pending()
        req = sch.request(rid)
        assert req.status == "done", req.error
        m1 = scrape()
    finally:
        httpd.shutdown()
        httpd.server_close()
    wall = time.perf_counter() - t0
    rows = ins.spans.snapshot()
    first = {}
    for r in rows:
        first.setdefault(r["name"], r["t0"])
    missing = [n for n in LIFECYCLE if n not in first]
    assert not missing, f"lifecycle spans missing: {missing}"
    order = [first[n] for n in LIFECYCLE]
    assert order == sorted(order), \
        f"lifecycle spans out of order: {list(zip(LIFECYCLE, order))}"
    assert any(r["name"] == "serve.settle" and r.get("rid") == rid
               for r in rows), "settle span lost its request id"
    # scrape monotonicity: every counter sample and histogram series
    # (bucket/sum/count) must be >= across the run; gauges may move
    # either way and are exempt
    mono = [k for k in m0 if k.endswith("_total")
            or "_bucket{" in k or k.endswith("_sum")
            or k.endswith("_count")]
    regressed = {k: (m0[k], m1.get(k)) for k in mono
                 if m1.get(k, 0) < m0[k]}
    assert not regressed, f"metrics regressed across scrapes: {regressed}"
    assert m1["wtpu_serve_submits_total"] \
        == m0["wtpu_serve_submits_total"] + 1, (m0, m1)
    phases = sch.health_stats().get("phases", {})
    assert "serve.queue_wait" in phases, phases
    return {"metric": "spans_smoke_spans", "value": len(rows),
            "unit": "spans", "wall_s": round(wall, 2),
            "lifecycle": list(LIFECYCLE),
            "metrics_series": len(m1),
            "phases": phases,
            "platform": jax.default_backend()}


def bench_catalog_smoke():
    """Program-observatory smoke stage (PR 20): one request through a
    catalog-attached scheduler, asserting the whole observatory seam
    end to end in seconds — a COLD build round-trips one durable
    catalog row (compile key, backend, compile wall, memory_analysis
    bytes, cost_analysis flops, the build-time cost-model
    predictions), the drift and registry gauges land on a REAL-HTTP
    `/w/batch/metrics` scrape, and `/w/batch/programs` serves the
    report (top compile-wall consumers + drift pass) over the same
    server."""
    import os
    import tempfile
    import threading
    import time
    import urllib.request

    import wittgenstein_tpu.models  # noqa: F401 — fills the registry
    from wittgenstein_tpu.obs.metrics import parse_exposition
    from wittgenstein_tpu.obs.programs import ProgramCatalog, read_catalog
    from wittgenstein_tpu.serve import ScenarioSpec, Scheduler
    from wittgenstein_tpu.serve.instrument import Instrumentation
    from wittgenstein_tpu.server.http import make_server

    spec = ScenarioSpec(protocol="PingPong", params={"node_count": 64},
                        seeds=(0,), sim_ms=120, chunk_ms=40,
                        obs=("metrics",))
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "programs.jsonl")
        ins = Instrumentation(worker="catalog_smoke")
        sch = Scheduler(instrument=ins, catalog=ProgramCatalog(path=path))
        httpd = make_server(port=0, batch_auto=False, scheduler=sch)
        port = httpd.server_address[1]
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{port}"
        t0 = time.perf_counter()
        try:
            rid = sch.submit(spec)
            sch.run_pending()
            req = sch.request(rid)
            assert req.status == "done", req.error
            with urllib.request.urlopen(f"{base}/w/batch/metrics",
                                        timeout=10) as resp:
                m = parse_exposition(resp.read().decode())
            with urllib.request.urlopen(f"{base}/w/batch/programs",
                                        timeout=10) as resp:
                rep = json.loads(resp.read())
        finally:
            httpd.shutdown()
            httpd.server_close()
        wall = time.perf_counter() - t0
        # the cold build left exactly one durable, fully-populated row
        rows = read_catalog(path)
    assert len(rows) == 1, [r.get("key") for r in rows]
    row = rows[0]
    for field in ("key", "plane", "backend", "compile_wall_s",
                  "memory", "cost", "predicted", "build_wall_s"):
        assert field in row, (field, sorted(row))
    assert row["compile_wall_s"] > 0 and row["build_wall_s"] > 0, row
    assert row["predicted"]["route_vmem_bytes"] > 0, row["predicted"]
    # drift + registry gauges on the real-HTTP scrape
    assert m.get("wtpu_programs_cataloged") == 1, m
    assert m.get("wtpu_registry_misses", 0) >= 1, m
    drift_series = [k for k in m if k.startswith("wtpu_costmodel_drift{")]
    assert drift_series, sorted(k for k in m if k.startswith("wtpu_"))
    # the /w/batch/programs report names the build in its top table
    assert rep["count"] == 1 and rep["top_compile"], rep
    assert rep["top_compile"][0]["key"] == row["key"], rep["top_compile"]
    assert any(d.get("vmem_ratio") for d in rep["drift"]), rep["drift"]
    return {"metric": "catalog_smoke_programs", "value": len(rows),
            "unit": "programs", "wall_s": round(wall, 2),
            "compile_wall_s": round(row["compile_wall_s"], 3),
            "drift_series": len(drift_series),
            "vmem_ratio": rep["drift"][0].get("vmem_ratio"),
            "platform": jax.default_backend()}


#: the search_smoke stage's boundary question — module-level like
#: MEMO_SMOKE_GRID (a consumer of its digest can never drift from the
#: stage): a single-slice 6-step loss ladder whose done_frac >= 0.99
#: verdict flips at p060 — coarse endpoints + 2 bisection probes
#: answer it in 4 of 6 cells, every probe forked off ONE shared
#: honest-prefix chunk
SEARCH_SMOKE_SPEC = {
    "name": "search_smoke",
    "grid": {
        "name": "search_smoke_grid",
        "base": {"protocol": "PingPong", "params": {"node_count": 32},
                 "seeds": [0], "sim_ms": 160, "chunk_ms": 40,
                 "obs": ["metrics", "audit"],
                 "latency_model": "NetworkFixedLatency(50)"},
        "axes": [
            {"name": "loss", "field": "fault_schedule",
             "values": [{"loss": [[40, 160, p, 0, 32, 0, 32]]}
                        for p in range(0, 120, 20)],
             "labels": ["p%03d" % p for p in range(0, 120, 20)]},
        ],
    },
    "axis": "loss",
    "predicate": {"field": "summary.done_frac", "op": ">=",
                  "value": 0.99},
    "coarse": 2,
}


def bench_search_smoke():
    """Adaptive-search smoke stage (PR 19): the module-level boundary
    question through `run_search` with memoized probes — asserting the
    whole seam in seconds: a boundary found with FEWER cells probed
    than the lattice holds, `prefix_chunks_saved` > 0 (probes forked
    off the shared honest prefix), the `SearchReport` JSON
    round-tripping bit-for-bit, and every probe's ledger row labelled
    ``search:<cell>`` with the search digest in its extra block."""
    import os
    import tempfile

    import wittgenstein_tpu.models  # noqa: F401 — fills the registry
    from wittgenstein_tpu.matrix import SearchReport, SearchSpec, \
        run_search
    from wittgenstein_tpu.obs import ledger
    from wittgenstein_tpu.serve import Scheduler

    spec = SearchSpec.from_json(SEARCH_SMOKE_SPEC)
    with tempfile.TemporaryDirectory() as tmp:
        led = os.path.join(tmp, "ledger.jsonl")
        run = run_search(spec, Scheduler(ledger_path=led))
        rep = run.report
        d = rep.data
        assert rep.clean, d["slices"]
        [sl] = d["slices"]
        assert sl["boundary_label"] == "p060", sl
        assert d["cells_probed"] < d["cells_exhaustive"], d
        assert d["chunks_simulated"] < d["chunks_exhaustive"], d
        memo = d["accounting"]["memo"]
        assert memo["prefix_chunks_saved"] > 0, memo
        assert memo["fork_vetoed"] == 0, memo
        # report round trip (schema-pinned load, atomic save path)
        again = SearchReport.from_json(
            json.dumps(rep.to_json(), sort_keys=True))
        assert again.to_json() == rep.to_json()
        # every probe left a ledger row labelled search:<cell> that
        # carries the search digest — the cross-campaign dedup join key
        rows = ledger.read_all(led)
        probe_rows = {r.run: r for r in rows
                      if r.run.startswith("search:")}
        assert set(probe_rows) == {
            f"search:{p['cell']}" for p in d["probes"]}, \
            sorted(probe_rows)
        assert all((r.extra or {}).get("search_digest")
                   == d["search_digest"] for r in probe_rows.values())
        assert any(r.run.startswith("memo:prefix:") for r in rows)
    return {"metric": "search_smoke_cells_probed",
            "value": d["cells_probed"], "unit": "cells",
            "cells_exhaustive": d["cells_exhaustive"],
            "chunks_simulated": d["chunks_simulated"],
            "chunks_exhaustive": d["chunks_exhaustive"],
            "probe_savings_ratio": d["probe_savings_ratio"],
            "prefix_chunks_saved": memo["prefix_chunks_saved"],
            "boundary": sl["boundary_label"],
            "search_digest": d["search_digest"],
            "grid_digest": d["grid_digest"],
            "platform": jax.default_backend()}


CONFIGS = {
    "pingpong_1000n": bench_pingpong,
    "gsf_4096n": bench_gsf,
    "sanfermin_32768n": bench_sanfermin,
    "dfinity_10k_validators": bench_dfinity,
    "trace_smoke": bench_trace_smoke,
    "audit_smoke": bench_audit_smoke,
    "serve_smoke": bench_serve_smoke,
    "chaos_smoke": bench_chaos_smoke,
    "matrix_smoke": bench_matrix_smoke,
    "tenancy_smoke": bench_tenancy_smoke,
    "memo_smoke": bench_memo_smoke,
    "crash_smoke": bench_crash_smoke,
    "fleet_smoke": bench_fleet_smoke,
    "spans_smoke": bench_spans_smoke,
    "analysis_smoke": bench_analysis_smoke,
    "search_smoke": bench_search_smoke,
    "catalog_smoke": bench_catalog_smoke,
}

# Stages whose metric is not a throughput number: the error path must
# emit the SAME metric name as the success path, or a consumer keying
# on it never sees the failure line.
METRIC_NAMES = {"trace_smoke": "trace_smoke_events",
                "audit_smoke": "audit_smoke_violations",
                "serve_smoke": "serve_smoke_requests",
                "chaos_smoke": "chaos_smoke_lost_msgs",
                "matrix_smoke": "matrix_smoke_cells",
                "tenancy_smoke": "tenancy_smoke_requests",
                "memo_smoke": "memo_smoke_prefix_chunks_saved",
                "crash_smoke": "crash_smoke_bit_identical",
                "fleet_smoke": "fleet_smoke_requests",
                "spans_smoke": "spans_smoke_spans",
                "analysis_smoke": "analysis_smoke_wall_s",
                "search_smoke": "search_smoke_cells_probed",
                "catalog_smoke": "catalog_smoke_programs"}


def _stage_spec(name):
    """Each tracked stage's static config as a `ScenarioSpec` — the
    suite's half of the one-config-path contract (bench.py builds its
    spec from the env; the stages are hard-coded configs, so their
    specs mostly are too).  The knobs `run_config` DOES honor from the
    env (WTPU_SUPERSTEP, the WTPU_METRICS/TRACE/AUDIT plane gates via
    bench's `_maybe_engine_metrics` chain) fold into the spec the same
    way, so a K=4 suite row can never digest equal to a K=1 row.

    The digest covers the REQUESTED config (the raw env K, before
    `run_config`'s pick_superstep demotion): equal digests therefore
    imply equal programs (demotion is deterministic), while the
    manifest's own `superstep` field records the EFFECTIVE K the run
    executed (run_config puts it in the line).  Returns None for
    unlisted/ad-hoc stage names."""
    import os

    from wittgenstein_tpu.serve.spec import ScenarioSpec
    env_ss = _env_superstep()       # run_config's own parse, shared
    env_obs = tuple(
        p for p, on in (
            ("metrics", os.environ.get("WTPU_METRICS", "1") != "0"),
            ("trace", os.environ.get("WTPU_TRACE") == "1"),
            ("audit", os.environ.get("WTPU_AUDIT", "1") != "0")) if on)
    table = {
        "pingpong_1000n": dict(
            protocol="PingPong", params={"node_count": 1000},
            seeds=tuple(range(4)), sim_ms=800, chunk_ms=100),
        "gsf_4096n": dict(
            protocol="GSFSignature", params={"node_count": 4096},
            seeds=tuple(range(4)), sim_ms=2500, chunk_ms=250),
        "sanfermin_32768n": dict(
            protocol="SanFermin",
            # box_split=2 is applied via cfg replace in bench_sanfermin
            # — program-affecting, so it must be in the digest even
            # though the ctor cannot express it (provenance capture,
            # never built)
            params={"node_count": 32768, "inbox_cap": 16,
                    "box_split": 2},
            seeds=(0,), sim_ms=6000, chunk_ms=500),
        "dfinity_10k_validators": dict(
            protocol="Dfinity",
            params={"block_producers_count": 10,
                    "attesters_count": 10_000,
                    "attesters_per_round": 100, "block_capacity": 512},
            seeds=(0,), sim_ms=120_000, chunk_ms=2000),
        "trace_smoke": dict(
            protocol="PingPong", params={"node_count": 64}, seeds=(0,),
            sim_ms=120, chunk_ms=120, obs=("trace",),
            trace_capacity=1024, superstep=1),
        "audit_smoke": dict(
            protocol="PingPong", params={"node_count": 64}, seeds=(0,),
            sim_ms=120, chunk_ms=120, obs=("audit",), superstep=1),
        "serve_smoke": dict(
            protocol="PingPong", params={"node_count": 64}, seeds=(0,),
            sim_ms=120, chunk_ms=120, obs=("metrics", "audit"),
            superstep=1),
        "chaos_smoke": dict(
            protocol="PingPong", params={"node_count": 64}, seeds=(0,),
            sim_ms=120, chunk_ms=120, obs=("audit",), superstep=1,
            fault_schedule=CHAOS_SMOKE_SCHEDULE),
        # the stage drives several tenants; the digested config is its
        # canonical campaign-tenant spec (tenancy fields are digest-
        # only, so this is the honest "what program ran" record)
        "tenancy_smoke": dict(
            protocol="PingPong", params={"node_count": 64}, seeds=(0,),
            sim_ms=120, chunk_ms=40, obs=("metrics", "audit"),
            superstep=1, tenant="campaign"),
        # the stage runs a whole grid twice; the digested config is
        # the grid's BASE cell (the matrix_smoke convention would be a
        # grid digest, but the ledger's config digest is a spec digest
        # — the base cell is the honest one-spec record)
        "memo_smoke": dict(
            protocol="PingPong", params={"node_count": 64},
            latency_model="NetworkFixedLatency(10)", seeds=(0,),
            sim_ms=240, chunk_ms=40, obs=("metrics", "audit"),
            superstep=1),
        # the stage SIGKILLs a whole campaign; the digested config is
        # the crash grid's BASE cell (the memo_smoke convention)
        "crash_smoke": dict(
            protocol="PingPong", params={"node_count": 64}, seeds=(0,),
            sim_ms=120, chunk_ms=40, obs=("metrics", "audit"),
            superstep=1),
        # the stage drives a two-worker fleet; the digested config is
        # its canonical request spec (the crash_smoke convention)
        "fleet_smoke": dict(
            protocol="PingPong", params={"node_count": 64}, seeds=(0,),
            sim_ms=120, chunk_ms=40, obs=("metrics", "audit"),
            superstep=1),
        # the stage runs one catalogued request; the digested config
        # is that request's spec (the fleet_smoke convention)
        "catalog_smoke": dict(
            protocol="PingPong", params={"node_count": 64}, seeds=(0,),
            sim_ms=120, chunk_ms=40, obs=("metrics",), superstep=1),
        # the stage answers a whole boundary question; the digested
        # config is the search grid's BASE cell (the memo_smoke
        # convention — the search digest itself rides the result line)
        "search_smoke": dict(
            protocol="PingPong", params={"node_count": 32}, seeds=(0,),
            latency_model="NetworkFixedLatency(50)",
            sim_ms=160, chunk_ms=40, obs=("metrics", "audit"),
            superstep=1),
    }
    cfg = table.get(name)
    if cfg is None:
        return None
    # smoke stages pin their own planes/K (stage-intrinsic); the four
    # run_config-driven stages take the env-honored values
    cfg.setdefault("obs", env_obs)
    cfg.setdefault("superstep", env_ss)
    return ScenarioSpec(**cfg)


def _append_ledger(name, res):
    """One provenance row per emitted suite line; the config digest is
    the stage's `ScenarioSpec` digest (`obs.ledger.append_from_spec` —
    the one config path bench.py and serve share; unlisted stages fall
    back to the env capture).  ``WTPU_LEDGER=0`` skips.  Never raises
    into the suite loop."""
    import os
    if os.environ.get("WTPU_LEDGER", "1") == "0":
        return
    from wittgenstein_tpu.obs import ledger
    spec = _stage_spec(name)
    if spec is not None:
        ledger.append_from_spec(res, spec, label=name, stage=name,
                                engine=res.get("engine", "vmapped"))
    else:
        ledger.append_from_env(res, label=name, stage=name,
                               engine="vmapped")  # run_config's scan_chunk


def _append_history(history, name, res, round_id):
    """One history row per emitted suite line (the regression gate's
    input — obs/regress.py).  Error lines append with empty measures
    (the detector skips them, but the round stays visible in the
    ledger).  ``WTPU_HISTORY=0`` or ``--no-history`` skips.  Never
    raises into the suite loop."""
    from wittgenstein_tpu.obs import regress
    spec = _stage_spec(name)
    history.append(
        stage=name, measures=regress.stage_measures(res),
        round_id=round_id,
        config_digest=spec.digest() if spec is not None else None,
        backend=res.get("platform"), metric=res.get("metric"))


def main(argv=None) -> int:
    import argparse
    import os
    import time

    ap = argparse.ArgumentParser(
        description="multi-config benchmark suite (one JSON line per "
        "stage); appends a bench-history row per stage and can gate "
        "the round against same-host baselines")
    ap.add_argument("stages", nargs="*", metavar="config",
                    help=f"stages to run (default: all; known: "
                    f"{', '.join(CONFIGS)})")
    ap.add_argument("--history",
                    default=str(REPO / "reports" / "bench_history.jsonl"),
                    help="bench-history ledger path (default: "
                    "reports/bench_history.jsonl)")
    ap.add_argument("--no-history", action="store_true",
                    help="skip history appends (WTPU_HISTORY=0 does "
                    "the same)")
    ap.add_argument("--check-regressions", action="store_true",
                    help="after the round, run the median/MAD gate "
                    "(obs/regress.py) over the history and exit 1 on "
                    "a regression")
    args = ap.parse_args(argv)

    names = args.stages or list(CONFIGS)
    write_history = not args.no_history and \
        os.environ.get("WTPU_HISTORY", "1") != "0"
    hist = None
    round_id = str(time.time_ns())
    if write_history:
        from wittgenstein_tpu.obs.regress import BenchHistory
        hist = BenchHistory(args.history)
    failed = []
    for name in names:
        metric = METRIC_NAMES.get(name, f"{name}_agg_sim_ms_per_sec")
        try:
            res = CONFIGS[name]()
            if "metric" not in res:
                res = {"metric": metric, **res}
        except Exception as e:                  # noqa: BLE001 — per-config
            # the other stages still run; the suite's exit code says
            # that this one failed
            res = {"metric": metric,
                   "error": f"{type(e).__name__}: {e!s:.300}"}
        if "error" in res:
            failed.append(name)
        _append_ledger(name, res)
        if hist is not None:
            _append_history(hist, name, res, round_id)
        print(json.dumps(res), flush=True)
    if failed:
        print(f"bench_suite: {len(failed)} stage(s) failed: "
              f"{', '.join(failed)}", file=sys.stderr)
    if args.check_regressions:
        if hist is None:
            print("bench_suite: --check-regressions needs history "
                  "appends on", file=sys.stderr)
            return 2
        from wittgenstein_tpu.obs import regress
        code, findings, summary = regress.gate(args.history,
                                               round_id=round_id)
        print(json.dumps({"metric": "regression_gate", "exit": code,
                          **summary}), flush=True)
        if findings:
            print(regress.format_findings(findings), file=sys.stderr)
        return code or int(bool(failed))
    return int(bool(failed))


if __name__ == "__main__":
    sys.exit(main())
