"""Tier-3 evidence run: 1M-node cardinal Handel.

Builds HandelCardinal at node_count=2^20 and either (a) GSPMD-shards the
node axis over an n-device virtual CPU mesh (the same layout
dryrun_multichip validates), or (b) with WTPU_CARDINAL_PLATFORM=tpu runs
single-device on the REAL chip (state 11.7 GB vs 16 GB HBM; the mailbox
ring is split into node-range sub-planes, EngineConfig.box_split, of at
most 800 MB each).  Runs
>= 100 simulated ms and asserts zero drops/clamps/evictions.  Writes
reports/CARDINAL_<label>.md — every config-dependent value in the
report prose is derived from the live config (the r3 template hardcoded
them, which produced a mislabeled report; BENCH_NOTES.md postmortem).

Usage:  python tools/cardinal_1m.py [sim_ms]    (default 120)
Env:    WTPU_CARDINAL_N (default 2^20), WTPU_CARDINAL_DEVS (default 8),
        WTPU_CARDINAL_PLATFORM=tpu (real chip, forces DEVS=1),
        WTPU_CARDINAL_SPLIT (box_split override)
"""

import pathlib
import resource
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import os  # noqa: E402

from wittgenstein_tpu.utils.platform import force_virtual_cpu  # noqa: E402

ON_TPU = os.environ.get("WTPU_CARDINAL_PLATFORM") == "tpu"
# WTPU_CARDINAL_DEVS=1 runs unsharded on one device: the GSPMD pipeline
# at N=2^20 x 8 partitions needs more compile/exec workspace than this
# 125 GB host has; the 1-device run proves tier-3 state + engine at 1M,
# and the mesh path is separately proven at smaller N (dryrun equality)
# and at the largest N the host fits.
N_DEV = 1 if ON_TPU else int(os.environ.get("WTPU_CARDINAL_DEVS", 8))
if not ON_TPU:
    # 8 virtual devices time-slice ONE physical core here, so the
    # per-device compute between collectives (minutes at 1M nodes) far
    # exceeds XLA:CPU's default 40 s rendezvous termination timeout —
    # raise both timeouts; on a real 8-chip mesh devices run
    # concurrently and the skew disappears.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_cpu_collective_call_warn_stuck_timeout_seconds=3600"
        " --xla_cpu_collective_call_terminate_timeout_seconds=86400"
    ).strip()
    force_virtual_cpu(N_DEV)

import jax                                         # noqa: E402
import jax.numpy as jnp                            # noqa: E402
import numpy as np                                 # noqa: E402
from jax.sharding import (Mesh, NamedSharding,     # noqa: E402
                          PartitionSpec as P)

from wittgenstein_tpu.core.network import scan_chunk   # noqa: E402
from wittgenstein_tpu.models.handel_cardinal import (  # noqa: E402
    HandelCardinal)


def main():
    import dataclasses
    import os
    sim_ms = int(sys.argv[1]) if len(sys.argv) > 1 else 120
    n = int(os.environ.get("WTPU_CARDINAL_N", 1 << 20))   # override: smoke
    # horizon 128 keeps the flat mailbox ring under the int32 index limit
    # (128 * 2^20 * 4 entries per plane); NetworkUniformLatency(100)
    # keeps every arrival inside the ring, so nothing can clamp or drop.
    # horizon 96 > Uniform(90)'s max one-way latency + 2, so every
    # arrival fits the ring (nothing may clamp); the tighter ring plus
    # cardinal's 2-word messages keep the donated state ~13 GB on a
    # 15.75 GB chip (the hz128/3-word config measured 17.16 GB — OOM).
    # inbox default 4 sized for 1M HBM fit; at 131k traffic it measured
    # 86k drops over 200 sim-ms — override per run (the zero-drop assert
    # below is the arbiter).
    inbox_cap = int(os.environ.get("WTPU_CARDINAL_INBOX", 4))
    queue_cap = int(os.environ.get("WTPU_CARDINAL_QUEUE", 8))
    proto = HandelCardinal(
        node_count=n, threshold=int(0.99 * n), nodes_down=0,
        pairing_time=4, dissemination_period_ms=20, fast_path=10,
        queue_cap=queue_cap, inbox_cap=inbox_cap, horizon=96,
        network_latency_name="NetworkUniformLatency(90)")
    # Ring sub-planes of at most 800 MB (at 2^20 x hz128 x ic4 a
    # monolithic plane is 2.1 GB -> split 4 ways, 537 MB each).  Sized
    # for a ~1 GB buffer limit of an earlier remote runtime; a v5e runs a
    # 1.5 GB buffer (PR 21 chip run), and the layout is bit-identical.
    plane_bytes = 4 * proto.cfg.horizon * n * proto.cfg.inbox_cap
    min_split = max(1, -(-plane_bytes // (800 * 1024 * 1024)))
    # Round up to a power of two: box_split must divide the (power-of-two)
    # node count.
    pow2_split = 1 << (min_split - 1).bit_length()
    split = int(os.environ.get("WTPU_CARDINAL_SPLIT",
                               pow2_split if ON_TPU else 1))
    if split > 1:
        proto.cfg = dataclasses.replace(proto.cfg, box_split=split)

    devices = jax.devices()[:N_DEV]
    mesh = Mesh(np.array(devices), ("sp",))

    def shard_spec(x):
        # Single seed (no leading batch axis): shard any size-n axis over
        # 'sp'; flat ring arrays shard across their flat index space.
        matches = [i for i in range(x.ndim) if x.shape[i] == n]
        spec = [None] * x.ndim
        if matches:
            spec[matches[-1]] = "sp"
        elif x.ndim == 1 and x.shape[0] >= n and x.shape[0] % (n * N_DEV) == 0:
            spec[0] = "sp"
        return NamedSharding(mesh, P(*spec))

    t0 = time.perf_counter()
    net, ps = jax.jit(proto.init)(jnp.asarray(0, jnp.int32))
    int(jax.device_get(net.time))           # host copy = completion proof
    t_init = time.perf_counter() - t0
    print(f"init: {t_init:.1f}s", flush=True)

    net = jax.tree.map(lambda x: jax.device_put(x, shard_spec(x)), net)
    ps = jax.tree.map(lambda x: jax.device_put(x, shard_spec(x)), ps)

    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves((net, ps)))
    print(f"state: {state_bytes / 1e9:.2f} GB across {N_DEV} shards",
          flush=True)

    # 20 = the config's schedule lcm (pairing 4, period 20): the
    # phase-specialized scan applies from t=0 (bit-identical,
    # tests/test_phase_hints.py) and chunk boundaries stay aligned.
    chunk = 20
    if ON_TPU:
        # Plain per-ms scan on the chip: the phase-specialized block
        # unrolls 20 step bodies whose staggered buffer lifetimes cost
        # 63% HBM fragmentation at 2^20 nodes (8.35 GB wasted — OOM,
        # observed 2026-07-31); the uniform per-ms body keeps temp
        # small.  This run proves FIT + correctness; the fused/phased
        # paths are the throughput configuration (bit-identical either
        # way, tests/test_superstep.py + test_phase_hints.py).
        base_step = scan_chunk(proto, chunk)
    else:
        # superstep=2: fused 2-ms engine pass — halves per-ms fixed
        # cost on the virtual-mesh runs.
        base_step = scan_chunk(proto, chunk, t0_mod=0, superstep=2)
    # Whole-state donation: without it the carry cannot alias the
    # 11.7 GB input state and the program needs 17.9 GB of HLO temp
    # against 15.75 GB of HBM.
    step = jax.jit(base_step, donate_argnums=(0, 1))
    t0 = time.perf_counter()
    with mesh:
        net, ps = step(net, ps)
        int(jax.device_get(net.time))
    t_compile = time.perf_counter() - t0
    print(f"first chunk ({chunk} ms incl. compile): {t_compile:.1f}s",
          flush=True)

    t0 = time.perf_counter()
    steps = (sim_ms - chunk + chunk - 1) // chunk
    with mesh:
        for i in range(steps):
            net, ps = step(net, ps)
        # Materialize every asserted value INSIDE the timed window: the
        # host copies are the completion proof (block_until_ready alone
        # measured dispatch, not compute, on this runtime — BENCH_NOTES
        # round-4 postmortem).
        total_ms = int(jax.device_get(net.time))
        dropped = int(jax.device_get(net.dropped))
        clamped = int(jax.device_get(net.clamped))
        bc_dropped = int(jax.device_get(net.bc_dropped))
        evicted = int(jax.device_get(ps.evicted))
        lvl_sum = np.asarray(jax.device_get(
            1 + jnp.sum(ps.lvl_best, axis=1)))
    t_run = time.perf_counter() - t0
    per_ms = t_run / max(1, steps * chunk)
    peak_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6

    print(f"time={total_ms}ms wall={t_run:.1f}s ({per_ms:.2f}s/sim-ms) "
          f"dropped={dropped} clamped={clamped} bc_dropped={bc_dropped} "
          f"evicted={evicted}", flush=True)
    print(f"aggregate progress: mean={lvl_sum.mean():.1f} "
          f"max={lvl_sum.max()} of {n}", flush=True)
    print(f"peak RSS: {peak_rss:.1f} GB", flush=True)

    assert total_ms >= sim_ms, (total_ms, sim_ms)
    assert dropped == 0 and clamped == 0 and bc_dropped == 0, (
        dropped, clamped, bc_dropped)
    # Aggregation must actually be progressing (counts grow past own sig).
    assert lvl_sum.max() > 1

    cfg = proto.cfg
    label = f"{n // 1024}k_{N_DEV}dev"
    if ON_TPU:
        label += "_tpu"
        plat = jax.devices()[0].device_kind
        topo = (f"single REAL {plat} chip ({jax.default_backend()} "
                f"backend), mailbox ring split into {cfg.box_split} "
                "node-range sub-planes (EngineConfig.box_split) of at "
                "most 800 MB each")
        per_chip = (f"measured here directly: {state_bytes / 1e9:.2f} GB "
                    "resident on one chip's 16 GB HBM.")
    elif N_DEV > 1:
        topo = (f"GSPMD node-axis sharding over a {N_DEV}-device virtual "
                f"CPU mesh (`xla_force_host_platform_device_count="
                f"{N_DEV}`, the same layout "
                "`__graft_entry__.dryrun_multichip` validates)")
        per_chip = (f"it shards evenly over the node axis, so a v5e-8 "
                    f"holds {state_bytes / 1e9 / N_DEV:.1f} GB/chip "
                    "against 16 GB HBM.")
    else:
        topo = ("UNSHARDED single virtual CPU device (GSPMD at this N x 8 "
                "partitions exceeds the host's compile/exec workspace; "
                "the mesh path is proven separately by the smaller-N "
                "mesh run and dryrun_multichip's bit-equality check)")
        per_chip = (f"on a v5e-8 the node axis shards it to "
                    f"{state_bytes / 1e9 / 8:.1f} GB/chip against "
                    "16 GB HBM.")
    report = REPO / "reports" / f"CARDINAL_{label}.md"
    report.write_text(f"""# Cardinal-mode {n:,}-node run ({N_DEV} device{"s" if N_DEV > 1 else ""})

Evidence for SCALE.md tier 3: `HandelCardinal` at N = {n:,} nodes,
{topo}, single seed.

Config: threshold 0.99N, pairing {proto.pairing_time} ms, period
{proto.period} ms, fastPath {proto.fast_path}, queue_cap
{proto.queue_cap}, inbox_cap {cfg.inbox_cap}, horizon {cfg.horizon},
{proto.latency!r} (all arrivals inside the ring by construction —
nothing may clamp).

| metric | value |
|---|---|
| simulated ms | {total_ms} |
| init wall-clock | {t_init:.1f} s |
| first {chunk}-ms chunk (incl. compile) | {t_compile:.1f} s |
| steady-state wall per sim-ms | {per_ms:.2f} s ({"real TPU chip" if ON_TPU else "1-core CPU host"}) |
| device state | {state_bytes / 1e9:.2f} GB across {N_DEV} device(s) |
| peak host RSS | {peak_rss:.1f} GB |
| dropped / clamped / bc_dropped / evicted | {dropped} / {clamped} / {bc_dropped} / {evicted} |
| aggregate count (mean / max over nodes) | {lvl_sum.mean():.1f} / {lvl_sum.max()} |

State is O(N*L): lvl_best [N, {proto.levels}] + queue counts, vs the
exact mode's Theta(N^2) bitsets (>= 0.8 TB at 1M — SCALE.md).  The
mailbox ring ({cfg.payload_words} x {cfg.horizon} x {n:,} x
{cfg.inbox_cap} int32 words + src/size/count) dominates at this scale;
{per_chip}

{"Measured on the real chip: fit, correct execution and honest per-ms cost at 1M-class N on one device."
 if ON_TPU else
 "Wall-clock caveat: this host is a 1-core CPU; the run validates fit + correct execution, not speed.  The per-sim-ms cost above is an upper bound that a real 8-chip mesh shrinks by the usual 2-3 orders."}
""")
    print(f"wrote {report}", flush=True)


if __name__ == "__main__":
    main()
