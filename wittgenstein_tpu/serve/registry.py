"""`CompileRegistry` — compile-key -> jitted chunk program, warm-start.

The request plane's latency story has two layers:

  * in-process: the registry memoizes ONE jitted chunk callable per
    (compile key, plane).  A repeat spec returns the SAME callable
    object — jax's jit cache then reuses the compiled executable for a
    previously-seen batch width, so a warm submit never re-traces or
    re-compiles (tests/test_serve.py pins callable identity, the
    `ab_plane_barrier` distinct-executables assert inverted);
  * cross-process: construction enables the PR-2 persistent compile
    cache (`harness.enable_persistent_cache`), so even a cold registry
    in a fresh service process compiles a previously-seen shape from
    the on-disk cache instead of from scratch.

Hit/miss counters are exported through the obs block conventions
(`registry_block()` — one flat JSON-able dict, like
`engine_metrics_block`/`audit_block`) and projected as
``wtpu_registry_{hits,misses}`` gauges into ``GET /w/batch/metrics``
(serve/instrument.refresh_scheduler_metrics).

Set-up is counted where it happens: one process-wide `jax.monitoring`
listener (`compile_counters`, installed once) sums the seconds JAX
spends tracing and lowering (`trace_s`: where the protocol's lazy
tables are built) and compiling or loading from the persistent cache
(`compile_s`), and counts that cache's hits and misses.
`compile_stats()` reports them since the registry was made, in
`registry_block()` and as ``wtpu_compile_*`` counters in
``GET /w/batch/metrics``.  They stay out of `stats()`, which request
artifacts carry: wall seconds and cache state are not results.

Every chunk program is jitted under a stable name that says its engine
and plane (`chunk_batched`, `chunk_vmapped_metrics`, ...), so its
module is ``jit_chunk_batched`` in every compile and device trace.

With a `ProgramCatalog` attached (``catalog=``, default None = zero
cost beyond one is-None branch), every cold build returns an
`obs.programs.CatalogProgram` instead of the bare jit wrapper: the
program's first launch AOT-compiles for the observed shapes, serves
the launch FROM that executable, and appends the program's catalog
row (compile walls, memory/cost analysis, the build-time cost-model
predictions staged here via `record_build`).
"""

from __future__ import annotations

import bisect
import threading

import jax

from ..core.harness import enable_persistent_cache
from .spec import ScenarioSpec

#: jax.monitoring events the compile counters read.  Trace and lower
#: spans nest (an inner jit is traced inside its caller), so each
#: group's seconds are the UNION of its spans.  The backend compile span
#: encloses the persistent cache's read: on a hit, `compile_s` is the
#: load.
TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"   # entry written
#: separate spans a group keeps for a later, enclosing span to merge
#: with; older ones are only counted.  A span reported after more than
#: this many separate spans it encloses counts the oldest of them twice
#: (tracing a Handel chunk program encloses about 1,900).
KEEP_SPANS = 1 << 16


class CompileCounters:
    """Set-up work of this process so far, from JAX's monitoring events,
    as running totals: a reader takes the difference of two `totals`
    readings, and the memory stays bounded however long the process
    lives."""

    _LOCK_OWNS = {"_mu": ("_spans", "_totals")}

    def __init__(self):
        self._mu = threading.Lock()
        #: the latest disjoint [start, end] spans per group, sorted
        self._spans = {"trace_s": [], "compile_s": []}
        self._totals = {"trace_s": 0.0, "compile_s": 0.0,
                        "cache_hits": 0, "cache_misses": 0}

    def install(self):
        from jax import monitoring
        monitoring.register_event_time_span_listener(self._on_span)
        monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, start, end, **_):
        group = ("trace_s" if event in TRACE_EVENTS else
                 "compile_s" if event == COMPILE_EVENT else None)
        if group is None:
            return
        with self._mu:
            spans = self._spans[group]
            lo = bisect.bisect_left(spans, start, key=lambda iv: iv[1])
            hi = bisect.bisect_right(spans, end, key=lambda iv: iv[0])
            if lo < hi:
                start = min(start, spans[lo][0])
                end = max(end, spans[hi - 1][1])
            self._totals[group] += (end - start) - sum(
                b - a for a, b in spans[lo:hi])
            spans[lo:hi] = [(start, end)]
            del spans[:-KEEP_SPANS]

    def _on_event(self, event, **_):
        name = {CACHE_HIT_EVENT: "cache_hits",
                CACHE_MISS_EVENT: "cache_misses"}.get(event)
        if name is not None:
            with self._mu:
                self._totals[name] += 1

    def totals(self) -> dict:
        """Seconds and counts since the listener was installed:
        ``trace_s``, ``compile_s``, ``cache_hits``, ``cache_misses``."""
        with self._mu:
            return dict(self._totals)


_COUNTERS: CompileCounters | None = None
_COUNTERS_MU = threading.Lock()


def compile_counters() -> CompileCounters:
    """The process's `CompileCounters`, its listener installed on the
    first call."""
    global _COUNTERS
    with _COUNTERS_MU:
        if _COUNTERS is None:
            _COUNTERS = CompileCounters()
            _COUNTERS.install()
        return _COUNTERS


class CompileRegistry:
    """See module docstring.  Thread-compat: `chunk_fn` is called under
    the scheduler's lock; the jitted callables themselves are safe to
    call concurrently."""

    def __init__(self, persistent: bool = True, catalog=None):
        self.cache_dir = enable_persistent_cache() if persistent else None
        #: program observatory (obs/programs.ProgramCatalog; None =
        #: OFF, the default — never imported, one is-None branch)
        self.catalog = catalog
        self._programs: dict = {}
        self.hits = 0
        self.misses = 0
        self.compiles = compile_counters()
        self._compiles0 = self.compiles.totals()

    # ------------------------------------------------------------- lookup

    def chunk_fn(self, spec: ScenarioSpec, plane: str | None = None,
                 proto=None):
        """The jitted chunk program for a RESOLVED spec (superstep int —
        `ScenarioSpec.validate` output) and one obs plane (None = the
        plain uninstrumented engine).  `proto` lets a caller that has
        already built the spec's protocol (the scheduler builds one per
        GROUP) share it — construction is heavy host work at tier-2
        sizes, so a cold multi-plane build must not repeat it.

        Return convention follows the engine builders: ``(nets, ps)``
        plain, ``(nets, ps, stats)`` fast-forward, with the plane's
        carry appended last when a plane is on — callers index
        ``out[0], out[1], out[-1]``."""
        if not isinstance(spec.superstep, int):
            raise ValueError("chunk_fn needs a resolved spec "
                             "(ScenarioSpec.validate() output): "
                             f"superstep={spec.superstep!r}")
        key = (spec.compile_key(), plane)
        fn = self._programs.get(key)
        if fn is not None:
            self.hits += 1
            return fn
        self.misses += 1
        fn = self._build(spec, plane, proto=proto)
        self._programs[key] = fn
        return fn

    def has_key(self, compile_key: str) -> bool:
        """True when ANY plane's program for this compile key is warm
        — the fleet workers' claim-affinity probe (serve/fleet.py
        prefers journal entries it can run without a fresh build, so
        compile keys specialize across a fleet instead of every worker
        rebuilding every program)."""
        return any(k[0] == compile_key for k in self._programs)

    # ------------------------------------------------------------ builders

    def _build(self, spec: ScenarioSpec, plane: str | None, proto=None):
        cat = self.catalog
        t_build = 0.0 if cat is None else cat.now()
        proto = proto if proto is not None else spec.build_protocol()
        ms, k, eng = spec.chunk_ms, spec.superstep, spec.engine
        if plane is None:
            from ..core.network import fast_forward_chunk, scan_chunk
            if eng == "batched":
                from ..core.batched import scan_chunk_batched
                base = scan_chunk_batched(proto, ms, superstep=k)
            elif eng == "fast_forward":
                base = fast_forward_chunk(proto, ms, seed_axis=True,
                                          superstep=k)
            else:
                base = jax.vmap(scan_chunk(proto, ms, superstep=k))
        elif plane == "metrics":
            from ..obs.engine import (fast_forward_chunk_metrics,
                                      scan_chunk_batched_metrics,
                                      scan_chunk_metrics)
            from ..obs.spec import MetricsSpec
            mspec = MetricsSpec(stat_each_ms=spec.stat_each_ms)
            if eng == "batched":
                base = scan_chunk_batched_metrics(proto, ms, mspec,
                                                  superstep=k)
            elif eng == "fast_forward":
                base = fast_forward_chunk_metrics(proto, ms, mspec,
                                                  seed_axis=True,
                                                  superstep=k)
            else:
                base = jax.vmap(scan_chunk_metrics(proto, ms, mspec,
                                                   superstep=k))
        elif plane == "trace":
            from ..obs.trace import (TraceSpec, fast_forward_chunk_trace,
                                     scan_chunk_batched_trace,
                                     scan_chunk_trace)
            tspec = TraceSpec(capacity=spec.trace_capacity)
            if eng == "batched":
                base = scan_chunk_batched_trace(proto, ms, tspec,
                                                superstep=k)
            elif eng == "fast_forward":
                base = fast_forward_chunk_trace(proto, ms, tspec,
                                                seed_axis=True,
                                                superstep=k)
            else:
                base = jax.vmap(scan_chunk_trace(proto, ms, tspec,
                                                 superstep=k))
        elif plane == "audit":
            from ..obs.audit import (AuditSpec, fast_forward_chunk_audit,
                                     scan_chunk_audit,
                                     scan_chunk_batched_audit)
            aspec = AuditSpec()
            if eng == "batched":
                base = scan_chunk_batched_audit(proto, ms, aspec,
                                                superstep=k)
            elif eng == "fast_forward":
                base = fast_forward_chunk_audit(proto, ms, aspec,
                                                seed_axis=True,
                                                superstep=k)
            else:
                base = jax.vmap(scan_chunk_audit(proto, ms, aspec,
                                                 superstep=k))
        else:
            raise ValueError(f"unknown obs plane {plane!r}; known: "
                             "metrics trace audit (or None)")
        base.__name__ = base.__qualname__ = (
            f"chunk_{eng}" if plane is None else f"chunk_{eng}_{plane}")
        # Pin the spec's routing-kernel selection around every call —
        # tracing happens inside the FIRST call, and a process-level
        # WTPU_PALLAS_ROUTE must never flip what this compile key
        # claims was built (route_kernel is a program field).
        if cat is None:
            from ..ops.pallas_route import with_route
            return with_route(jax.jit(base), spec.route_kernel)
        # catalog path: stage the build-time facts (host construction
        # wall + the cost-model predictions, which need proto.cfg) and
        # hand the launch seam an AOT-capturing wrapper — it runs the
        # program under the same forced route pin `with_route` would.
        from ..obs.programs import CatalogProgram
        cat.record_build(spec, plane, proto.cfg,
                         build_wall_s=cat.now() - t_build)
        return CatalogProgram(jax.jit(base), spec.route_kernel, cat,
                              spec.compile_key(), plane)

    # ------------------------------------------------------------- export

    def stats(self) -> dict:
        return {"entries": len(self._programs), "hits": self.hits,
                "misses": self.misses,
                "persistent_cache": self.cache_dir or "off"}

    def compile_stats(self) -> dict:
        """The process's trace and compile work since this registry was
        made (`CompileCounters.totals`)."""
        now = self.compiles.totals()
        return {k: now[k] - self._compiles0[k] for k in now}

    def registry_block(self, extra: dict | None = None) -> dict:
        """The ``registry`` block for bench JSON / service status
        (schema: BENCH_NOTES.md r11) — the warm/cold story of every
        submit, in the same one-flat-dict convention as
        `engine_metrics_block`."""
        out = {**self.stats(), **self.compile_stats()}
        if extra:
            out.update(extra)
        return out
