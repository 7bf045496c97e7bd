"""Experiment harness: Monte-Carlo multi-seed runs + time-series collection.

The reference runs seeds sequentially (core/RunMultipleTimes.java:41-87:
``p.copy(); rd.setSeed(i); init(); runMs(10) while contIf``).  Here all seeds
run **at once**: `init` and the per-ms step are vmapped over a seed axis, so a
256-seed sweep is one device program — the DP analogue promised in SURVEY §2.6.

Per-run stopping is faithful: after every `chunk` simulated ms (the
reference's 10 ms granularity) each run's continue-predicate is evaluated
in-kernel and finished runs are *frozen* (their state no longer changes), so
every run's final state is exactly its state at its own stop time, and stats
match the sequential semantics run for run.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from ..utils import stats as stats_mod
from .network import pick_superstep, scan_chunk


def enable_persistent_cache(cache_dir=None):
    """Enable JAX's persistent compilation cache (default:
    ``reports/jax_cache/``, repo-local and gitignored) so processes
    that compile the same programs (repeated bench runs, A/Bs, the
    chip smoke) stop paying full recompiles.  The default is a FIXED
    path: the directory is part of the cache key, so a path derived
    from a temporary name, a pid or the time would never hit.

    Respects an existing configuration: a caller (tests/conftest.py,
    analysis/targets.py) or the JAX_COMPILATION_CACHE_DIR env var —
    which JAX itself mirrors into `jax_compilation_cache_dir`, so no
    ambient read happens here — wins; the env var set to "" disables
    caching entirely.  Returns the cache directory in effect (None when
    disabled)."""
    import pathlib

    # Cache-everything thresholds apply regardless of who picked the
    # directory (the defaults skip fast-compiling programs, which is
    # most of a small-config suite/bench).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    existing = jax.config.jax_compilation_cache_dir
    if existing is not None:            # env var or an earlier caller
        return existing or None         # "" = explicitly disabled
    if cache_dir is None:
        cache_dir = str(pathlib.Path(__file__).resolve().parents[2]
                        / "reports" / "jax_cache")
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    return str(cache_dir)


def cache_entry_count(cache_dir) -> int:
    """Number of entries currently in the persistent compile cache —
    sampled before/after a compile, the delta is the honest hit/miss
    signal the bench logs (JAX exposes no per-lookup counter)."""
    import os

    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def cont_until_done(net, pstate):
    """RunMultipleTimes.contUntilDone (:90-97): continue while any live node
    has doneAt == 0."""
    live = ~net.nodes.down
    return jnp.any(live & (net.nodes.done_at == 0))


def _freeze_chunk(protocol, chunk, cont, t0=0):
    """Jitted: advance every run by `chunk` ms, keeping stopped runs frozen
    at their stop-time state.  `t0` is the runs' ACTUAL entry time (read
    from the initialized state, not assumed 0)."""

    # Every run's time is t0 + a multiple of `chunk` at chunk boundaries
    # (frozen runs stop exactly on one), so when `chunk` is a multiple
    # of the protocol's static schedule lcm the phase-specialized scan
    # applies to every run at phase ``t0 % lcm`` (bit-identical —
    # tests/test_phase_hints.py).  The fused superstep applies under the
    # same alignment argument: ALL alignment decisions — chunk length,
    # entry time, schedule compatibility — route through the shared
    # K-aware gate (`pick_superstep`/`check_chunk_config`), so an entry
    # time that is not K-aligned demotes to a smaller window instead of
    # silently fusing across a misaligned boundary (the historical
    # chunk-parity-only gate missed exactly that —
    # tests/test_harness.py::test_odd_entry_time_demotes_superstep).
    lcm = getattr(protocol, "schedule_lcm", None)
    use_spec = bool(lcm and chunk % lcm == 0)
    ss = pick_superstep(protocol, chunk, t0=t0,
                        lcm=lcm if use_spec else None)
    one_chunk = scan_chunk(protocol, chunk,
                           t0_mod=(t0 % lcm) if use_spec else None,
                           superstep=ss)

    @jax.jit
    def chunk_all(nets, ps, stopped, stopped_at):
        nets2, ps2 = jax.vmap(one_chunk)(nets, ps)

        def sel(old, new):
            shape = (stopped.shape[0],) + (1,) * (new.ndim - 1)
            return jnp.where(stopped.reshape(shape), old, new)

        nets3 = jax.tree.map(sel, nets, nets2)
        ps3 = jax.tree.map(sel, ps, ps2)
        still = jax.vmap(cont)(nets3, ps3)
        newly_stopped = (~stopped) & (~still)
        stopped_at = jnp.where(newly_stopped, nets3.time, stopped_at)
        dropped = (jnp.sum(nets3.dropped) + jnp.sum(nets3.bc_dropped) +
                   jnp.sum(nets3.clamped) + jnp.sum(nets3.sp_dropped))
        return nets3, ps3, stopped | ~still, stopped_at, dropped

    return chunk_all


def _check_drops(dropped, where):
    if int(dropped) > 0:
        raise RuntimeError(
            f"{int(dropped)} messages dropped/clamped during {where}: the "
            "protocol's inbox_cap / bcast_slots / horizon are undersized for "
            "this scenario (pass fail_on_drop=False if that is intended)")


def _shard_seed_axis(trees, devices):
    """Lay the leading (seed) axis of every array across `devices` with a
    1-D GSPMD mesh — the multi-device analog of the reference's sequential
    seed loop (RunMultipleTimes.java:44-76).  Runs are data-parallel with
    no cross-run ops, so XLA partitions the whole chunk program along the
    seed axis and results stay bit-identical to the single-device vmap."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("dp",))

    def put(x):
        spec = P(*(("dp",) + (None,) * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return tuple(jax.tree.map(put, t) for t in trees)


def _shard_seed_and_node_axes(trees, mesh, n):
    """2-D sweep layout: the leading (seed) axis over the mesh's 'dp' axis
    and the node axis (any later axis of size `n`, last match wins, with a
    warning when non-adjacent matches make the pick ambiguous; flat
    mailbox axes divisible by n*sp are sharded across their flat index
    space) over 'sp'.  This is the multi-slice topology of SURVEY §2.6 —
    on real hardware 'dp' is the DCN/inter-slice axis (runs never
    communicate) and 'sp' the ICI axis (node-state collectives stay
    in-slice); on one host it validates on a virtual mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sp = mesh.shape["sp"]

    def put(x):
        matches = [i for i in range(1, x.ndim) if x.shape[i] == n]
        spec = [None] * x.ndim
        spec[0] = "dp"
        contiguous = matches == list(range(matches[0], matches[-1] + 1)) \
            if matches else False
        if len(matches) > 1 and not contiguous:
            # An unrelated axis (inbox_cap, payload_words, ...) coinciding
            # with n makes the choice ambiguous: GSPMD stays correct either
            # way but silently inserts reshards, defeating the intended ICI
            # layout.  Surface it instead of guessing quietly.
            import warnings
            warnings.warn(
                f"node-axis sharding is ambiguous for leaf shape {x.shape}: "
                f"axes {matches} all have size n={n}; using axis "
                f"{matches[-1]}. Pick a node count that no other axis "
                "coincides with, or shard explicitly (see "
                "__graft_entry__.shard_spec).", stacklevel=2)
        if matches:
            # Last match wins: for the hot [R, horizon, n] double-match
            # (box_count with horizon == n, the Handel default) the node
            # axis IS the last axis (__graft_entry__.shard_spec documents
            # this), and for a pairwise [n, n] emission block either pick
            # is GSPMD-correct.  A contiguous run is therefore resolved
            # silently; only non-adjacent matches warrant the warning.
            spec[matches[-1]] = "sp"
        elif x.ndim == 2 and x.shape[1] >= n and x.shape[1] % (n * sp) == 0:
            spec[1] = "sp"
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    return tuple(jax.tree.map(put, t) for t in trees)


class _BatchDriver:
    """Shared multi-seed scaffolding for `run_multiple_times` and
    `progress_per_time`: vmapped init over seeds, frozen-run chunk advance,
    and the drop/clamp guard."""

    def __init__(self, protocol, run_count, chunk, cont_if, first_seed,
                 fail_on_drop, where, devices=None, mesh=None):
        # Repeated experiment sweeps recompile the same chunk programs;
        # the persistent cache makes every run after the first ~free
        # (no-op when a caller/env already configured or disabled it).
        enable_persistent_cache()
        self.cont = cont_if or cont_until_done
        self.seeds = jnp.arange(first_seed, first_seed + run_count,
                                dtype=jnp.int32)
        self.nets, self.ps = jax.vmap(protocol.init)(self.seeds)
        self.stopped = jnp.zeros((run_count,), bool)
        self.stopped_at = jnp.zeros((run_count,), jnp.int32)
        trees = (self.nets, self.ps, self.stopped, self.stopped_at,
                 self.seeds)
        if mesh is not None:
            if devices is not None:
                raise ValueError("pass either devices or mesh, not both")
            if run_count % mesh.shape["dp"] != 0:
                raise ValueError(f"run_count={run_count} not divisible by "
                                 f"the mesh 'dp' axis ({mesh.shape['dp']})")
            if protocol.cfg.n % mesh.shape["sp"] != 0:
                raise ValueError(f"node count {protocol.cfg.n} not "
                                 f"divisible by 'sp' ({mesh.shape['sp']})")
            trees = _shard_seed_and_node_axes(trees, mesh, protocol.cfg.n)
            (self.nets, self.ps, self.stopped, self.stopped_at,
             self.seeds) = trees
        else:
            explicit = devices is not None
            if devices is None:                  # auto: all, when they divide
                devices = jax.devices()
                if run_count % len(devices) != 0:
                    devices = devices[:1]
            if run_count % len(devices) != 0:
                raise ValueError(f"run_count={run_count} not divisible by "
                                 f"{len(devices)} devices")
            # Place even for an explicit single device (it may not be the
            # default one); skip only the redundant auto single-device put.
            if len(devices) > 1 or explicit:
                (self.nets, self.ps, self.stopped, self.stopped_at,
                 self.seeds) = _shard_seed_axis(trees, devices)
        # The runs' ACTUAL entry time (a protocol's init may start the
        # clock anywhere) — the superstep/phase alignment decisions in
        # _freeze_chunk are made against it, never assumed.
        import numpy as np
        t0 = int(np.asarray(jax.device_get(self.nets.time)).reshape(-1)[0])
        self._chunk_all = _freeze_chunk(protocol, chunk, self.cont, t0=t0)
        self._fail_on_drop = fail_on_drop
        self._where = where

    def advance(self):
        """One chunk for every run; returns True when all runs have stopped."""
        (self.nets, self.ps, self.stopped, self.stopped_at,
         dropped) = self._chunk_all(self.nets, self.ps, self.stopped,
                                    self.stopped_at)
        if self._fail_on_drop:
            _check_drops(dropped, self._where)
        return bool(jnp.all(self.stopped))


@dataclasses.dataclass
class MultiRunResult:
    nets: object          # NetState batch, leading run axis; each frozen at its stop time
    pstates: object       # protocol state batch
    stopped_at: jnp.ndarray   # int32 [R] — sim time when each run stopped (0 = ran to max)
    stats: dict           # getter name -> averaged stat dict (floats)
    per_run: dict         # getter name -> stat dict with leading run axis


def run_multiple_times(protocol, run_count, max_time=0, chunk=10,
                       cont_if=None, stats_getters=(), final_check=None,
                       first_seed=0, fail_on_drop=True, devices=None,
                       max_wall_s=None, mesh=None):
    """Vectorized RunMultipleTimes.run (RunMultipleTimes.java:41-87).

    Seeds are first_seed..first_seed+run_count-1 (the reference uses the
    round index as seed, :46).  max_time=0 mirrors the reference's
    "no time limit" — the loop then runs until every run's predicate stops
    it; unlike the reference there is no ^C ergonomics under jit, so a
    wall-clock bound (`max_wall_s`, default 1800 s when max_time=0) guards
    against a protocol that cannot converge.  `devices` shards the seed
    axis across a device mesh (default: all local devices when run_count
    divides evenly; pass `devices=jax.devices()[:1]` to force one).
    `mesh` (a Mesh with axes 'dp' and 'sp', mutually exclusive with
    `devices`) lays seeds over 'dp' AND the node axis over 'sp' — the
    multi-slice topology where 'dp' rides DCN and node-state collectives
    stay on in-slice ICI (SURVEY §2.6).
    Returns averaged stats across runs plus per-run values.
    """
    drv = _BatchDriver(protocol, run_count, chunk, cont_if, first_seed,
                       fail_on_drop, f"run_multiple_times({protocol})",
                       devices=devices, mesh=mesh)
    steps = 10**9 if max_time == 0 else -(-max_time // chunk)
    if max_time == 0 and max_wall_s is None:
        max_wall_s = 1800.0
    deadline = None if max_wall_s is None else time.monotonic() + max_wall_s
    for _ in range(steps):
        if drv.advance():
            break
        if deadline is not None and time.monotonic() > deadline:
            raise RuntimeError(
                f"run_multiple_times({protocol}) exceeded the "
                f"{max_wall_s:.0f}s wall-clock bound at sim time "
                f"{int(jnp.max(drv.nets.time))} ms with "
                f"{int(jnp.sum(~drv.stopped))}/{run_count} runs still "
                "going; pass max_time or a larger max_wall_s")
    nets, ps, stopped_at, seeds = drv.nets, drv.ps, drv.stopped_at, drv.seeds

    if final_check is not None:
        ok = jax.vmap(final_check)(nets, ps)
        if not bool(jnp.all(ok)):
            bad = [int(s) for s in seeds[~ok]]
            raise AssertionError(f"finalCheck failed for seeds {bad}")

    per_run, averaged = {}, {}
    for g in stats_getters:
        vals = jax.vmap(lambda net: g(net.nodes))(nets)
        per_run[g.stat_name] = vals
        averaged[g.stat_name] = stats_mod.avg_stats(vals)
    return MultiRunResult(nets=nets, pstates=ps, stopped_at=stopped_at,
                          stats=averaged, per_run=per_run)


@dataclasses.dataclass
class TimeSeries:
    times: list           # sample times (ms)
    per_run: dict         # getter name -> list over time of stat dicts [R]
    merged: dict          # "<getter>.<component>" -> {"min"/"max"/"avg": [...]}


def progress_per_time_on_device(protocol, run_count=1, max_time=20_000,
                                stat_each_ms=10, counters=None,
                                first_seed=0, fast_forward=False):
    """`progress_per_time` with the sampling moved ON DEVICE: one
    compiled chunk covers the whole span and the obs metrics plane
    (wittgenstein_tpu/obs) records the per-interval series as an extra
    scan/while carry — no host round trip per sample period, which is
    what lets a 10k-ms scan be observed without serializing the device
    on the host every `stat_each_ms`.

    Returns ``(frame, nets, pstates)``: an `obs.MetricsFrame` (exporter
    matrix: CSV / Perfetto / bench block) aggregated over the seed
    batch, plus the final states.  Differences from `progress_per_time`:
    the counter set is the engine plane's (obs/spec.py COUNTERS), not
    arbitrary stats getters, and runs are not frozen at their stop time
    (the whole batch advances `max_time` ms — protocol counters of a
    converged run simply flatline).  ``fast_forward=True`` uses the
    instrumented quiet-window engine (skipped intervals record
    ``samples == 0`` and forward-fill exactly)."""
    from ..obs import MetricsFrame, MetricsSpec
    from ..obs.engine import (fast_forward_chunk_metrics,
                              scan_chunk_metrics)

    enable_persistent_cache()
    spec = MetricsSpec(stat_each_ms=stat_each_ms,
                       **({"counters": tuple(counters)} if counters
                          else {}))
    seeds = jnp.arange(first_seed, first_seed + run_count,
                       dtype=jnp.int32)
    nets, ps = jax.vmap(protocol.init)(seeds)
    if fast_forward:
        run = jax.jit(fast_forward_chunk_metrics(protocol, max_time, spec,
                                                 seed_axis=True))
        nets, ps, _, mc = run(nets, ps)
    else:
        run = jax.jit(jax.vmap(scan_chunk_metrics(protocol, max_time,
                                                  spec)))
        nets, ps, mc = run(nets, ps)
    return MetricsFrame.from_carry(spec, mc), nets, ps


def capture_trace(protocol, ms: int, spec=None, seed=0,
                  fast_forward=False, superstep=1):
    """One-command flight-recorder capture: run `ms` simulated
    milliseconds of `protocol` from a fresh `seed` with the event trace
    plane on (wittgenstein_tpu/obs/trace.py) and return
    ``(TraceFrame, net, pstate)`` — the decoded message-level event
    stream plus the final state (bit-identical to an untraced run).

    The README "Observability" workflow entry point: from here
    `frame.format()` prints the timeline, `obs.trace_to_perfetto(frame,
    path)` renders it, and a truncated ring announces itself through
    ``frame.dropped``."""
    from ..obs.decode import TraceFrame
    from ..obs.trace import (TraceSpec, fast_forward_chunk_trace,
                             scan_chunk_trace)

    enable_persistent_cache()
    spec = spec or TraceSpec()
    net, pstate = protocol.init(jnp.asarray(seed, jnp.int32))
    if fast_forward:
        run = jax.jit(fast_forward_chunk_trace(protocol, int(ms), spec,
                                               superstep=superstep))
        net, pstate, _, tc = run(net, pstate)
    else:
        run = jax.jit(scan_chunk_trace(protocol, int(ms), spec,
                                       superstep=superstep))
        net, pstate, tc = run(net, pstate)
    return TraceFrame.from_carry(spec, tc), net, pstate


def progress_per_time(protocol, run_count=1, max_time=20_000,
                      stat_each_ms=10, stats_getters=(), cont_if=None,
                      first_seed=0, fail_on_drop=True, devices=None):
    """Time-series variant (core/ProgressPerTime.java:53-149): sample the
    getters every `stat_each_ms` across all runs; merge min/avg/max across
    the run axis per sample point.  Stopped runs are frozen exactly as in
    `run_multiple_times`, so each run's samples flatline at its own
    stop-time values (the sequential reference never samples a finished run
    again; a frozen flatline is the batched equivalent)."""
    drv = _BatchDriver(protocol, run_count, stat_each_ms, cont_if, first_seed,
                       fail_on_drop, f"progress_per_time({protocol})",
                       devices=devices)

    @jax.jit
    def sample(nets):
        return {g.stat_name: jax.vmap(lambda net: g(net.nodes))(nets)
                for g in stats_getters}

    times, series = [], {g.stat_name: [] for g in stats_getters}
    t = 0
    while t < max_time:
        all_stopped = drv.advance()
        t += stat_each_ms
        vals = sample(drv.nets)
        times.append(t)
        for k, v in vals.items():
            series[k].append(v)
        if all_stopped:
            break
    nets, ps = drv.nets, drv.ps

    # Merge across the run axis per sample point (Graph.statSeries,
    # tools/Graph.java:214-251): one "<getter>.<component>" series each for
    # min / max / avg across runs.
    merged = {}
    for k, samples in series.items():
        for comp in samples[0]:
            merged[f"{k}.{comp}"] = {
                "min": [float(jnp.min(s[comp])) for s in samples],
                "max": [float(jnp.max(s[comp])) for s in samples],
                "avg": [float(jnp.mean(s[comp])) for s in samples],
            }
    return TimeSeries(times=times, per_run=series, merged=merged), nets, ps
