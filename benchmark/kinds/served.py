"""`served`: closed-loop clients through an in-process `serve.Service`.

Each client submits a request (the cell's spec over its own seeds),
waits for it to settle, and submits the next, until `--seconds`; then
the requests in flight finish.  Parameters of a mix:

  clients            closed-loop clients (threads)
  seeds_per_request  seeds of each request (distinct across requests)
  sim_ms, chunk_ms   each request's span and the chunk it is driven in
  obs                the observation planes each request asks for
  trace_requests     requests each client submits in a ``--trace 1``
                     run (its traced window)
  check              {"requests": sampled finished requests}

Progress is counted at chunk boundaries from `Request.chunk_totals`:
`sim_ms_per_s` is the seed-sim-ms of every boundary crossed over the
time to the last settle.  The request plane's spans
(`serve.instrument`) give the launch and settle shares.  Correct: for
the sampled requests, the plain reference re-runs every seed over the
request's span and the summary sums (messages sent and received, live
nodes done) must equal the request's; a request that fails counts as
wrong.  The reference is the protocol's (`check.reference`), found
before anything is built.  A ``--trace 1`` run traces its window
(`bench.window`) and reduces the trace as the stream kind does: busy
time and idle gaps, no stage table.
"""

from __future__ import annotations

import shutil
import threading
import time

import numpy as np

from .. import check
from ..harness import device_block
from .stream import WARM_SEED0, batch_seeds, reduce_trace, reference_params

POLL_S = 0.01
#: the request artifact's summary values compared with the reference
SUMMARY = ("msg_sent", "msg_received", "done_count")


def _body(ctx, mix, seeds, attack=None):
    body = dict(ctx["config"]["spec"])
    body.update(seeds=[int(s) for s in seeds], sim_ms=mix["sim_ms"],
                chunk_ms=mix["chunk_ms"], obs=list(mix["obs"]),
                attack=attack)
    return body


def _serve_one(svc, body, timeout_s):
    rid = svc.submit(body)["id"]
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        res = svc.result(rid)
        if res.get("status") == "done":
            return rid, res
        if res.get("status") in ("error", "quarantined", "withdrawn"):
            raise RuntimeError(f"request {rid} ended {res.get('status')}: "
                               f"{res.get('error')}")
        time.sleep(POLL_S)
    raise TimeoutError(f"request {rid} did not settle in {timeout_s} s")


def _warm_up(svc, ctx, mix, warm) -> list:
    """Set-up: requests queued under one compile key run as one group,
    one program over all their seeds, so the clients can form a group of
    1 to `clients` requests.  Each such group runs once here, on
    throwaway seeds and drained in this thread (the service's own drain
    thread starts with the window's first submit), so that every
    program and shape the window can use is compiled.  Returns the
    errors of requests that did not settle."""
    from wittgenstein_tpu.serve.spec import ScenarioSpec
    sched, errors = svc.scheduler, []
    for k in range(mix["clients"], 0, -1):
        rids = [sched.submit(ScenarioSpec.from_json(_body(
            ctx, mix, warm + j * len(warm), ctx.get("attack"))))
            for j in range(k)]
        sched.run_pending()
        errors += [f"warm-up {rid}: {sched.request(rid).status}"
                   for rid in rids if sched.request(rid).status != "done"]
    return errors


def run(ctx: dict) -> dict:
    import jax

    import wittgenstein_tpu.models  # noqa: F401  (registers protocols)
    from wittgenstein_tpu.serve.instrument import (SPAN_LAUNCH,
                                                   SPAN_SETTLE,
                                                   Instrumentation)
    from wittgenstein_tpu.serve.scheduler import Scheduler
    from wittgenstein_tpu.serve.service import Service

    mix = ctx["traffic"]
    width, seed = mix["seeds_per_request"], ctx["seed"]
    ref = check.reference(ctx["root"], ctx["config"]["spec"]["protocol"])
    traced = ctx["trace"]
    out_dir = ctx["out_dir"]
    tdir = out_dir / "trace"
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "ledger.jsonl").unlink(missing_ok=True)
    ins = Instrumentation()
    svc = Service(Scheduler(ledger_path=str(out_dir / "ledger.jsonl"),
                            instrument=ins), auto=True)
    if ctx["patch"] is not None:       # a fault under every program
        reg = svc.scheduler.registry
        build = reg.chunk_fn
        reg.chunk_fn = lambda *a, **k: ctx["patch"](build(*a, **k))
    served, mu, errors = [], threading.Lock(), []
    try:
        t = time.monotonic()
        warm = np.arange(width, dtype=np.int32) + WARM_SEED0
        errors += _warm_up(svc, ctx, mix, warm)
        load_s = time.monotonic() - t
        if traced:
            shutil.rmtree(tdir, ignore_errors=True)
            jax.profiler.start_trace(str(tdir))
        compiles = svc.scheduler.registry.compile_stats()
        t_w0 = time.monotonic()
        s_w0 = ins.now()
        setup_s = ctx["pre_start"] + (t_w0 - ctx["t0"])
        counter = iter(range(1 << 30))

        def more(n):
            if traced:
                return n < mix["trace_requests"]
            return time.monotonic() - t_w0 < ctx["seconds"]

        def client():
            n = 0
            try:
                while more(n):
                    n += 1
                    with mu:
                        k = next(counter)
                    seeds = batch_seeds(seed, k, width)
                    rid, res = _serve_one(
                        svc, _body(ctx, mix, seeds, ctx.get("attack")),
                        3600)
                    with mu:
                        served.append((time.monotonic() - t_w0, rid, seeds,
                                       res))
            except Exception as e:   # reported as failed, never hidden
                with mu:
                    errors.append(repr(e))

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(mix["clients"])]
        with jax.profiler.TraceAnnotation("bench.window"):
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        if traced:
            jax.profiler.stop_trace()
        wall = max((s[0] for s in served), default=0.0)
        boundaries = sum(len(svc.scheduler.request(rid).chunk_totals)
                         for _, rid, _, _ in served)
        spans = [r for r in ins.spans.snapshot() if r["t0"] >= s_w0]
        window_compile_s = (svc.scheduler.registry.compile_stats()
                            ["compile_s"] - compiles["compile_s"])
        device = device_block(ctx["devices"])
    finally:
        svc.close()

    record = {
        "setup": {"setup_s": setup_s, "load_s": load_s,
                  "compile": compiles},
        "window": {"seconds": wall, "requests": len(served),
                   "seed_sim_ms": boundaries * mix["chunk_ms"] * width,
                   "lockstep_ms": boundaries * mix["chunk_ms"]},
        "serve": {"window_s": wall, "compile_s": window_compile_s,
                  "launch_s": sum(r["dur"] for r in spans
                                  if r["name"] == SPAN_LAUNCH),
                  "settle_s": sum(r["dur"] for r in spans
                                  if r["name"] == SPAN_SETTLE)},
        "device": device, "attempted": len(served) + len(errors)}
    if traced:
        record.update(reduce_trace(tdir, record))
    t = time.monotonic()
    rng = np.random.default_rng([seed % 2 ** 64, 1 << 21])
    pick = rng.choice(len(served), size=min(mix["check"]["requests"],
                                            len(served)), replace=False)
    from wittgenstein_tpu.serve.spec import ScenarioSpec
    spec = ScenarioSpec.from_json(_body(ctx, mix, warm)).validate()
    params = reference_params(spec)
    n_chunks = mix["sim_ms"] // mix["chunk_ms"]
    wrong, bad = 0, len(errors)
    for i in pick:
        _, _, seeds, res = served[int(i)]
        got = res["summary"]
        want = dict.fromkeys(SUMMARY, 0)
        for end in check.reference_trajectories(ref, params, seeds,
                                                mix["chunk_ms"], n_chunks):
            end = end[-1]
            live = ~np.asarray(end["down"])
            want["msg_sent"] += int(np.asarray(end["msg_sent"]).sum())
            want["msg_received"] += int(
                np.asarray(end["msg_received"]).sum())
            want["done_count"] += int(
                ((np.asarray(end["done_at"]) > 0) & live).sum())
        n = sum(got[k] != v for k, v in want.items())
        wrong += n
        bad += n > 0
    # a request that never settles, or a window with none to compare,
    # is an answer that never came: its summary values count as wrong
    wrong += len(SUMMARY) * (len(errors) + (not pick.size))
    record["checks"] = {"mismatched_values": {
        "value": wrong, "limit": check.LIMITS["mismatched_values"]}}
    record["failed"] = bad
    record["check_s"] = time.monotonic() - t
    return record
