"""`served`: closed-loop clients through an in-process `serve.Service`.

Each client submits a request (the cell's spec over its own seeds),
waits for it to settle, and submits the next, until `--seconds`; then
the requests in flight finish.  Parameters of a mix:

  clients            closed-loop clients (threads)
  seeds_per_request  seeds of each request (distinct across requests)
  sim_ms, chunk_ms   each request's span and the chunk it is driven in
  obs                the observation planes each request asks for
  check              {"requests": sampled finished requests}

Progress is counted at chunk boundaries from `Request.chunk_totals`:
`sim_ms_per_s` is the seed-sim-ms of every boundary crossed over the
time to the last settle.  The request plane's spans
(`serve.instrument`) give the launch and settle shares.  Correct: for
the sampled requests, the plain reference re-runs every seed over the
request's span and the summary sums (messages sent and received, live
nodes done) must equal the request's; a request that fails counts as
wrong.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from .. import check
from ..harness import device_block
from .stream import WARM_SEED0, batch_seeds, reference_params

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


def run(ctx: dict) -> dict:
    import wittgenstein_tpu.models  # noqa: F401  (registers protocols)
    from wittgenstein_tpu.serve.instrument import (SPAN_LAUNCH,
                                                   SPAN_SETTLE,
                                                   Instrumentation)
    from wittgenstein_tpu.serve.scheduler import Scheduler
    from wittgenstein_tpu.serve.service import Service

    mix = ctx["traffic"]
    width, seed = mix["seeds_per_request"], ctx["seed"]
    out_dir = ctx["out_dir"]
    out_dir.mkdir(parents=True, exist_ok=True)
    ins = Instrumentation()
    svc = Service(Scheduler(ledger_path=str(out_dir / "ledger.jsonl"),
                            instrument=ins), auto=True)
    try:
        # set-up: one request on throwaway seeds compiles every plane
        t = time.monotonic()
        warm = np.arange(width, dtype=np.int32) + WARM_SEED0
        _serve_one(svc, _body(ctx, mix, warm, ctx.get("attack")), 3600)
        load_s = time.monotonic() - t
        t_w0 = time.monotonic()
        s_w0 = ins.now()
        setup_s = ctx["pre_start"] + (t_w0 - ctx["t0"])
        served, mu, errors = [], threading.Lock(), []
        counter = iter(range(1 << 30))

        def client():
            try:
                while time.monotonic() - t_w0 < ctx["seconds"]:
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
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = max((s[0] for s in served), default=0.0)
        boundaries = sum(len(svc.scheduler.request(rid).chunk_totals)
                         for _, rid, _, _ in served)
        spans = [r for r in ins.spans.snapshot() if r["t0"] >= s_w0]
        device = device_block(ctx["devices"])
    finally:
        svc.close()

    record = {
        "setup": {"setup_s": setup_s, "load_s": load_s},
        "window": {"seconds": wall, "requests": len(served),
                   "seed_sim_ms": boundaries * mix["chunk_ms"] * width,
                   "lockstep_ms": boundaries * mix["chunk_ms"]},
        "serve": {"window_s": wall,
                  "launch_s": sum(r["dur"] for r in spans
                                  if r["name"] == SPAN_LAUNCH),
                  "settle_s": sum(r["dur"] for r in spans
                                  if r["name"] == SPAN_SETTLE)},
        "device": device, "attempted": len(served) + len(errors)}
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
        for ref in check.reference_trajectories(params, seeds,
                                                mix["chunk_ms"], n_chunks):
            ref = ref[-1]
            live = ~np.asarray(ref["down"])
            want["msg_sent"] += int(np.asarray(ref["msg_sent"]).sum())
            want["msg_received"] += int(
                np.asarray(ref["msg_received"]).sum())
            want["done_count"] += int(
                ((np.asarray(ref["done_at"]) > 0) & live).sum())
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
