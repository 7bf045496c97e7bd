"""Run the simulator's main path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: phases a, b, c
    python chip_smoke.py --four-chips  # four chips: the sharded path only

One process; nothing here starts a child that touches JAX (a chip
belongs to one process).  The compile cache goes through
`core.harness.enable_persistent_cache` ($JAX_COMPILATION_CACHE_DIR, else
the fixed in-checkout `reports/jax_cache/`).

Phases, each printing one JSON line of facts (walls, compile time,
`peak_bytes_in_use`, the counters it checked), run in the order b, c,
a; the programs of a and c compile in one thread while b runs
(`one_chip`):

  a. headline: `bench.py`'s Handel deployment (2048 nodes, 10% down,
     threshold 0.99 x live, exact mode, horizon 256, inbox 12, 16
     seeds, batched engine, superstep 2) through `bench._handel_setup`
     to 1000 simulated ms, under bench's own check (> 99% of live nodes
     done; zero drops, clamps, evictions), then the same seeds again,
     bit-identical.
  b. request plane: an in-process `serve.Service(auto=True)` answers
     Handel 2048 x 4 and PingPong 1000 x 4 (200 simulated ms each) with
     obs metrics+audit, then the Handel spec again: audits clean, a
     registry hit, and the duplicate's `engine_metrics` equal to the
     first bit for bit.  The line carries the request plane's own spans
     (`serve.instrument`): queue wait (the seed init), launch (the
     first launch of a program includes its compile), chunk, settle.
  c. kernels: a 200-ms headline window with `pallas_merge=True` (the
     merge and score kernels) and the route kernel forced on, compared
     bit for bit with the XLA path.  A kernel its launcher refuses at
     build time is named in the line and left out of the window.

`--four-chips` runs `__graft_entry__.sharded_equivalence` on
`jax.devices()[:4]` as a dp=2 x sp=2 mesh: Handel at 2048 nodes, the
GSPMD run and the exact-mode run, each bit-identical to the unsharded
run on device 0.

The last line is `{"ok": true, "device": {...}}` only when every phase
passed on a TPU; otherwise the exit code is non-zero and no such line
is printed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

#: the bench.py headline (HandelScenarios.java:61-123 defaults)
HEADLINE = dict(n=2048, seeds=16, sim_ms=1000, chunk=200, mode="exact",
                horizon=256, inbox_cap=12, superstep=2)


def _emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _host(tree):
    import jax
    return jax.tree.map(lambda x: jax.device_get(x), tree)


def _mismatches(a, b) -> list[str]:
    """Key paths of the leaves that differ between two host pytrees."""
    import jax
    import numpy as np
    return [jax.tree_util.keystr(p)
            for (p, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                                 jax.tree.leaves(b))
            if not np.array_equal(np.asarray(x), np.asarray(y))]


def _handel_counters(nets, ps) -> dict:
    import numpy as np
    done_at = np.asarray(nets.nodes.done_at)
    down = np.asarray(nets.nodes.down)
    live = ~down
    return {"time_ms": int(np.asarray(nets.time).max()),
            "live_done_frac": float((done_at > 0)[live].mean()),
            "dropped": int(np.asarray(nets.dropped).sum()),
            "bc_dropped": int(np.asarray(nets.bc_dropped).sum()),
            "clamped": int(np.asarray(nets.clamped).sum()),
            "evicted": int(np.asarray(ps.evicted).sum())}


def _compile(step, init):
    import jax
    t = time.perf_counter()
    compiled = step.lower(*jax.eval_shape(init)).compile()
    return compiled, time.perf_counter() - t


def _setup(cfg, pallas=False):
    """`bench._handel_setup` at `cfg`; ``pallas=True`` builds Handel
    with `pallas_merge` (the merge and score kernels) through bench's
    own `WTPU_BENCH_PALLAS` knob."""
    import os

    from bench import _handel_setup
    if pallas:
        os.environ["WTPU_BENCH_PALLAS"] = "1"
    try:
        return _handel_setup(cfg["n"], cfg["seeds"], cfg["sim_ms"],
                             cfg["chunk"], cfg["mode"], cfg["horizon"],
                             cfg["inbox_cap"], cfg["superstep"])
    finally:
        os.environ.pop("WTPU_BENCH_PALLAS", None)


def _compile_kernel_window(with_route, without_route):
    """Compile the kernel window with the route kernel forced on; when
    its launcher refuses at build time, name the refusal and compile
    the window without it."""
    from wittgenstein_tpu.ops.pallas_route import forced
    try:
        with forced("pallas"):
            return _compile(*with_route), {}
    except ValueError as e:
        if "pallas_route" not in str(e):
            raise
        refused = {"bin_into_ring_planes": str(e)}
    with forced("xla"):
        return _compile(*without_route), refused


def phase_headline(dev, head, compiled, compile_s, cfg=HEADLINE):
    """(a) The headline run through bench.py's own setup, twice."""
    import jax

    _, init, step_count, check, _, superstep, engine, _ = head

    def run():
        nets, ps = init()
        jax.block_until_ready((nets, ps))
        t = time.perf_counter()
        for _ in range(step_count):
            nets, ps = compiled(nets, ps)
        check(nets, ps)             # bench's asserts, inside the window
        wall = time.perf_counter() - t
        return _host((nets, ps)), wall

    first, wall1 = run()
    second, wall2 = run()
    mism = _mismatches(first, second)
    assert not mism, f"rerun differs from the first run at {mism[:8]}"
    sim = cfg["seeds"] * step_count * cfg["chunk"]
    _emit("headline", nodes=cfg["n"], seeds=cfg["seeds"],
          sim_ms=step_count * cfg["chunk"], engine=engine,
          superstep=superstep, compile_s=compile_s, wall_s=[wall1, wall2],
          agg_sim_ms_per_s=[sim / wall1, sim / wall2],
          rerun_bit_identical=True, peak_bytes_in_use=_peak_bytes(dev),
          **_handel_counters(*first))


def _await(svc, rid, timeout_s):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        res = svc.result(rid)
        if res["status"] == "done":
            return res
        if res["status"] == "error":
            raise RuntimeError(f"request {rid} failed: {res.get('error')}")
        time.sleep(0.2)
    raise TimeoutError(f"request {rid} not done within {timeout_s} s")


def _handel_params(n):
    down = n // 10
    return dict(node_count=n, threshold=int(0.99 * (n - down)),
                nodes_down=down, pairing_time=4, level_wait_time=50,
                dissemination_period_ms=20, fast_path=10, mode="exact",
                horizon=256, inbox_cap=12)


def phase_service(dev, handel_n=2048, pingpong_n=1000, seeds=4,
                  sim_ms=200, chunk_ms=200, timeout_s=900):
    """(b) Three requests through the in-process request plane."""
    import wittgenstein_tpu.models  # noqa: F401 — fills the registry
    from wittgenstein_tpu.serve import Instrumentation, Scheduler, Service

    common = dict(seeds=list(range(seeds)), sim_ms=sim_ms,
                  chunk_ms=chunk_ms, obs=["metrics", "audit"])
    handel = dict(protocol="Handel", params=_handel_params(handel_n),
                  engine="batched", superstep=2, **common)
    pingpong = dict(protocol="PingPong",
                    params={"node_count": pingpong_n}, **common)
    ins = Instrumentation()
    svc = Service(Scheduler(instrument=ins), auto=True)
    try:
        t0 = time.perf_counter()
        rids = [svc.submit(handel)["id"], svc.submit(pingpong)["id"]]
        first = [_await(svc, r, timeout_s) for r in rids]
        wall_first = time.perf_counter() - t0
        t1 = time.perf_counter()
        dup = _await(svc, svc.submit(handel)["id"], timeout_s)
        wall_dup = time.perf_counter() - t1
        reg = svc.registry_stats()
    finally:
        svc.close()
    for name, res in zip(("handel", "pingpong", "handel_dup"),
                         first + [dup]):
        assert res["audit"]["clean"], f"{name}: audit not clean"
    assert reg["hits"] >= 1, f"no registry hit: {reg}"
    assert dup["engine_metrics"] == first[0]["engine_metrics"], \
        "duplicate submit's engine_metrics differ from the first"
    spans = [[row["name"], row.get("rid") or row.get("key"),
              round(row["dur"], 3)] for row in ins.spans.snapshot()]
    _emit("service", handel_nodes=handel_n, pingpong_nodes=pingpong_n,
          seeds=seeds, sim_ms=sim_ms, wall_s=[wall_first, wall_dup],
          registry=reg, audit_clean=True, duplicate_bit_identical=True,
          handel_totals=first[0]["engine_metrics"]["totals"],
          pingpong_totals=first[1]["engine_metrics"]["totals"],
          peak_bytes_in_use=_peak_bytes(dev), spans=spans)


def phase_kernels(dev, window, xla_step, xla_init, cfg=HEADLINE):
    """(c) One 200-ms headline window with the Pallas kernels on, bit
    for bit against the XLA path (one chunk of the headline program)."""
    import jax

    (compiled, compile_s), refused = window
    kernels = ["merge_queue_pallas", "score_queue_pallas",
               "bin_into_ring_planes"]
    kernels = [k for k in kernels if k not in refused]
    assert "tpu_custom_call" in compiled.as_text() or \
        jax.default_backend() != "tpu", "no Pallas kernel in the program"
    t = time.perf_counter()
    got = _host(compiled(*xla_init()))
    wall = time.perf_counter() - t
    want = _host(xla_step(*xla_init()))
    mism = _mismatches(want, got)
    assert not mism, f"kernel window differs from XLA at {mism[:8]}"
    _emit("kernels", window_ms=cfg["chunk"], seeds=cfg["seeds"],
          kernels=kernels, refused=refused, compile_s=compile_s,
          wall_s=wall, bit_identical_to_xla=True,
          peak_bytes_in_use=_peak_bytes(dev),
          **_handel_counters(*got))


def one_chip(dev, cfg=HEADLINE, service=None):
    """Phases b, c, a.  One thread compiles the headline program, then
    the kernel window, while the device serves phase b: run one after
    another the phases need more than the driver's 1200 s (PERF.md,
    "Where the time goes"), so the compile walls a and c report
    overlap phase b."""
    import gc
    from concurrent.futures import ThreadPoolExecutor

    head = _setup(cfg)
    window = dict(cfg, sim_ms=cfg["chunk"])
    with_route, without_route = (_setup(window, pallas=True)[:2]
                                 for _ in range(2))

    def compile_programs():
        return (_compile(head[0], head[1]),
                _compile_kernel_window(with_route, without_route))

    with ThreadPoolExecutor(1) as pool:
        programs = pool.submit(compile_programs)
        phase_service(dev, **(service or {}))
        (compiled, compile_s), kernel_window = programs.result()
    gc.collect()                    # the service's programs and states
    phase_kernels(dev, kernel_window, compiled, head[1], cfg)
    del kernel_window, with_route, without_route
    gc.collect()                    # the kernel window's program
    phase_headline(dev, head, compiled, compile_s, cfg)


def phase_four_chips(devices):
    """The sharded path on four chips, against device 0."""
    from __graft_entry__ import sharded_equivalence
    t = time.perf_counter()
    facts = sharded_equivalence(devices, n=2048, exact_n=2048,
                                families=False)
    _emit("four_chips", wall_s=time.perf_counter() - t,
          peak_bytes_in_use=[_peak_bytes(d) for d in devices], **facts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dp=2 x sp=2 sharded path")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r} "
              f"({len(devices)} device(s)); run it on the chip",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from wittgenstein_tpu.core.harness import enable_persistent_cache
    _emit("setup", platform=dev.platform, kind=dev.device_kind,
          count=len(devices), jax=jax.__version__,
          compile_cache=enable_persistent_cache())
    if args.four_chips:
        phase_four_chips(devices[:4])
    else:
        one_chip(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
