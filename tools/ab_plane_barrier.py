"""Same-process A/B of the batched engine's plane-ordering barrier.

BENCH_NOTES.md round-4 rule: measured throughput varied widely
BETWEEN processes (identical programs measured 0-86 ms/sim-ms minutes
apart), so every perf comparison must interleave both variants within
one process.  This tool builds the headline Handel config twice —
barrier on (scatters update the mailbox planes in place) and barrier
off (XLA copy-insertion copies every plane per superstep,
tools/carry_audit.py) — and alternates timed reps A/B/A/B....

Results are bit-identical between variants (asserted on the first rep
pair: same final time/done_at checksums).

Usage: python tools/ab_plane_barrier.py [n] [seeds] [sim_ms] [reps]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2048
    seeds = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    sim_ms = int(sys.argv[3]) if len(sys.argv) > 3 else 1000
    reps = int(sys.argv[4]) if len(sys.argv) > 4 else 3
    chunk = 200

    # The A/B only measures the barrier when _handel_setup takes the
    # BATCHED path: an ambient WTPU_BENCH_BATCHED=0 would silently
    # compile the vmapped engine twice (which ignores plane_barrier)
    # and report a meaningless A/B of two identical programs
    # (ADVICE.md r5 item 3).  Force the batched path for both builds —
    # and force the quiet-window engine OFF: an ambient
    # WTPU_FAST_FORWARD=1 would swap in the while-loop engine, whose
    # wall time is skip-rate-dominated, mislabeling the barrier A/B.
    os.environ["WTPU_BENCH_BATCHED"] = "1"
    os.environ["WTPU_FAST_FORWARD"] = "0"

    import bench
    assert os.environ.get("WTPU_BENCH_BATCHED") != "0", \
        "WTPU_BENCH_BATCHED must not be 0 for the barrier A/B"

    def build(barrier: bool):
        os.environ["WTPU_PLANE_BARRIER"] = "1" if barrier else "0"
        return bench._handel_setup(n, seeds, sim_ms, chunk, "exact",
                                   256, 12, superstep=2)

    step_on, init, steps, check, _, _, _, _ = build(True)
    step_off, _, _, _, _, _, _, _ = build(False)
    os.environ.pop("WTPU_PLANE_BARRIER", None)

    # Prove the knob reached the compiler: the on/off builds must be
    # DISTINCT executables (the barrier is an ordering op in the
    # program; identical HLO means the A/B collapsed into A/A).  The
    # AOT-compiled executables then REPLACE the jit wrappers for the
    # timed reps — one compile per variant total, not two.
    args0 = init()
    step_on = step_on.lower(*args0).compile()
    step_off = step_off.lower(*args0).compile()
    hlo_on = step_on.as_text()
    hlo_off = step_off.as_text()
    assert hlo_on != hlo_off, \
        "barrier on/off compiled to IDENTICAL executables — the A/B " \
        "is not exercising the plane barrier (batched path not taken?)"
    print("distinct executables: OK "
          f"(on {len(hlo_on)} B, off {len(hlo_off)} B of HLO text)")

    def one_rep(step):
        nets, ps = init()
        np.asarray(nets.time)
        t0 = time.perf_counter()
        for _ in range(steps):
            nets, ps = step(nets, ps)
        check(nets, ps)                      # materialize inside window
        wall = time.perf_counter() - t0
        return wall, nets, ps

    # Warm both executables, and prove bit-equality of the variants.
    w_on, nets_a, ps_a = one_rep(step_on)
    w_off, nets_b, ps_b = one_rep(step_off)
    assert np.array_equal(np.asarray(nets_a.time), np.asarray(nets_b.time))
    assert np.array_equal(np.asarray(nets_a.nodes.done_at),
                          np.asarray(nets_b.nodes.done_at)), \
        "barrier changed results — it must be ordering-only"
    print(f"bit-equality: OK (warm walls on={w_on:.1f}s off={w_off:.1f}s)")

    walls_on, walls_off = [], []
    for i in range(reps):
        walls_on.append(one_rep(step_on)[0])
        walls_off.append(one_rep(step_off)[0])
        print(f"rep {i}: barrier_on {walls_on[-1]:.2f}s  "
              f"barrier_off {walls_off[-1]:.2f}s", flush=True)

    total = seeds * sim_ms
    r_on = total / float(np.median(walls_on))
    r_off = total / float(np.median(walls_off))
    print(f"AB_RESULT n={n} seeds={seeds} sim_ms={sim_ms} "
          f"barrier_on={r_on:.1f} barrier_off={r_off:.1f} "
          f"speedup={r_on / r_off:.3f}x")


if __name__ == "__main__":
    main()
