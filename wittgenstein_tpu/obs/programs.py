"""Program observatory — what the compiled programs themselves cost.

The host flight recorder (obs/spans.py, PR 18) sees wall-clock phases;
nothing so far recorded the DEVICE-PROGRAM side of those walls: how
long each compile key took to lower and compile, what the executable's
memory footprint is (`Compiled.memory_analysis()` — temp / argument /
output / generated-code bytes), what XLA estimates it costs
(`cost_analysis()` — flops, bytes accessed), and how the engine's own
hand-built VMEM cost models (`route_row_bytes`, `_pick_block` — the
models the `vmem_budget` analysis rule evaluates) track the measured
footprint.  That gap is ROADMAP item 2's measurement discipline:
validate the model against the machine, SCALE-Sim style, instead of
trusting constants.

`ProgramCatalog` is the durable record.  The serve registry
(serve/registry.py) hands each cold build a `CatalogProgram` wrapper;
on the program's FIRST launch the wrapper AOT-compiles the jitted
callable for the observed argument shapes (``jit.lower(*args)`` +
``.compile()``), serves the launch FROM that compiled executable (so
capture never compiles twice — the AOT executable IS the program the
chunks run), and appends one schema'd JSONL row through the sanctioned
`utils/jsonl.append_line` path: compile key, obs plane, backend, build
/ lower / compile walls, the memory analysis, the cost analysis, and
the cost-model predictions captured at build time.  Per-launch
chunk-wall samples then aggregate into the catalog (and, when the
PR-18 metrics registry is attached, into its
``wtpu_program_chunk_seconds`` histogram); the drift pass computes
predicted-vs-measured ratios per program.

Design constraints, in the spans.py order:

  * OFF costs nothing: the registry and scheduler hold
    ``catalog=None`` by default and guard every site with a plain
    is-None test — this module is never imported on the uncataloged
    path (tests/test_programs.py pins it).
  * Crash postmortems keep the catalog: every row goes through
    `utils/jsonl.append_line` (fsync'd by default — a catalog exists
    to survive the run that wrote it), so a SIGKILL mid-append leaves
    at most one torn tail `read_catalog` skips.  The
    ``host_durability`` rule covers this file in its strict zone.
  * Deterministic under an injected clock, like the span recorder.
  * Bit-identical simulation: the AOT executable is compiled from the
    same jaxpr the jit path would compile, under the same forced
    route-kernel pin; a shape the capture has not seen (width
    degradation, lane repack) falls back to the plain jit callable.
"""

from __future__ import annotations

import sys
import threading
import time

import jax

from ..utils import jsonl

#: catalog-row schema (bump on field changes)
SCHEMA = 1

#: the one-wave reference message count the build-time prediction
#: evaluates `route_fixed_bytes` at (the real m is launch-dependent;
#: the per-row slab term, which dominates, is m-independent)
PREDICT_M_REF = 256


def cost_model_predictions(cfg, route_kernel: str) -> dict:
    """The engine's OWN VMEM cost-model predictions for one program's
    routing kernel, evaluated at build time from the protocol's
    `NetConfig` — the same `route_row_bytes`/`_pick_route_block`
    model the launcher budgets with and the `vmem_budget` analysis
    rule checks.  ``enforce=False`` so a CPU-shaped config predicts
    instead of raising (the drift pass is exactly for finding out how
    wrong these numbers are)."""
    from ..ops.pallas_route import (_pick_route_block, _VMEM_BUDGET,
                                    ROUTE_CHUNK, route_fixed_bytes,
                                    route_row_bytes)
    h, c, f = int(cfg.horizon), int(cfg.inbox_cap), int(cfg.payload_words)
    ns = int(cfg.n)
    row = int(route_row_bytes(h, c, f))
    fixed = int(route_fixed_bytes(PREDICT_M_REF, f))
    blk = int(_pick_route_block(ns, PREDICT_M_REF, h, c, f,
                                chunk=ROUTE_CHUNK, enforce=False))
    return {"route_kernel": route_kernel,
            "route_row_bytes": row,
            "route_fixed_bytes": fixed,
            "route_block": blk,
            "route_vmem_bytes": fixed + blk * row,
            "vmem_budget_bytes": int(_VMEM_BUDGET),
            "m_ref": PREDICT_M_REF}


def _memory_block(compiled) -> dict:
    """`Compiled.memory_analysis()` as a plain dict (None when the
    backend does not implement it — provenance degrades softly, the
    obs contract)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:                               # noqa: BLE001
        return {}
    out = {}
    for field, name in (("temp_size_in_bytes", "temp_bytes"),
                        ("argument_size_in_bytes", "argument_bytes"),
                        ("output_size_in_bytes", "output_bytes"),
                        ("alias_size_in_bytes", "alias_bytes"),
                        ("generated_code_size_in_bytes", "code_bytes")):
        v = getattr(ma, field, None)
        if v is not None:
            out[name] = int(v)
    return out


def _cost_block(compiled) -> dict:
    """`Compiled.cost_analysis()` flops/bytes (a missing analysis
    degrades to {})."""
    try:
        ca = compiled.cost_analysis()
    except Exception:                               # noqa: BLE001
        return {}
    if not isinstance(ca, dict):
        return {}
    out = {}
    if "flops" in ca:
        out["flops"] = float(ca["flops"])
    if "bytes accessed" in ca:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out


def _args_signature(args):
    """Hashable shape/dtype signature of a launch's argument pytree —
    what decides whether the captured AOT executable can serve a
    call.  Tree STRUCTURE is part of the signature (two states with
    equal leaf shapes but different containers are different
    programs)."""
    leaves, treedef = jax.tree.flatten(args)
    return (treedef,
            tuple((tuple(getattr(x, "shape", ())),
                   str(getattr(x, "dtype", type(x).__name__)))
                  for x in leaves))


class CatalogProgram:
    """The registry's launch callable for one (compile key, plane)
    when a catalog is attached.  First call: AOT lower + compile under
    the spec's forced route kernel, record the catalog row, and serve
    the call from the compiled executable.  Matching-signature calls
    keep using that executable (zero re-trace, bit-identical by
    construction — it IS the program).  A new signature (batch-width
    degradation, lane repack) falls back to the plain jitted callable,
    whose own cache handles the new shape exactly as the uncataloged
    path would.

    Concurrency: launches are sequential per program (one drain
    thread), but a watchdog-abandoned launch thread may still be
    inside `__call__` when the retry enters it — capture state is
    therefore a single atomically-assigned ``_captured`` tuple, and
    `ProgramCatalog.record_program` dedupes the row under its lock."""

    def __init__(self, jit_fn, route_kernel: str, catalog, key: str,
                 plane):
        self._jit = jit_fn
        self._kind = route_kernel
        self._catalog = catalog
        self._key = key
        self._plane = plane
        self._captured = None       # (signature, compiled) after capture

    def __call__(self, *args):
        cap = self._captured
        sig = _args_signature(args)
        if cap is not None:
            if cap[0] == sig:
                return cap[1](*args)
            # degraded / repacked width: the jit path owns this shape
            from ..ops.pallas_route import forced
            with forced(self._kind):
                return self._jit(*args)
        from ..ops.pallas_route import forced
        cat = self._catalog
        with forced(self._kind):
            t0 = cat.now()
            lowered = self._jit.lower(*args)
            t1 = cat.now()
            compiled = lowered.compile()
            t2 = cat.now()
        shapes = [s for s, _ in sig[1]]
        cat.record_program(self._key, self._plane,
                           lower_wall_s=t1 - t0,
                           compile_wall_s=t2 - t1,
                           memory=_memory_block(compiled),
                           cost=_cost_block(compiled),
                           arg_leaves=len(shapes),
                           batch=(shapes[0][0] if shapes and shapes[0]
                                  else None))
        self._captured = (sig, compiled)
        return compiled(*args)


class ProgramCatalog:
    """Durable per-program telemetry: one JSONL row per compiled
    program (module docstring), plus in-memory chunk-wall aggregates
    and the drift pass.  Thread-safe: build rows land from the drain
    thread, chunk samples from drain/watchdog threads, reads from the
    HTTP scrape thread."""

    #: lock inventory (analysis rule ``host_locks``): `_mu` guards the
    #: program/pending tables, the per-key chunk aggregates and the
    #: degraded-write counter.
    _LOCK_OWNS = {"_mu": ("_programs", "_pending", "_chunks",
                          "_write_errors")}

    def __init__(self, path=None, *, fsync: bool = True, clock=None,
                 metrics=None, backend: str | None = None):
        #: durable JSONL catalog (None = in-memory only).  fsync
        #: defaults ON — unlike the span log, the catalog is sparse
        #: (one row per cold build) and exists to survive the run.
        self.path = str(path) if path else None
        self.fsync = bool(fsync)
        #: the ONLY time source (injectable for deterministic tests)
        self.clock = clock if clock is not None else time.perf_counter
        #: optional PR-18 `MetricsRegistry`: chunk-wall samples feed
        #: its ``wtpu_program_chunk_seconds`` histogram (the scheduler
        #: shares its `Instrumentation` registry here when both are on)
        self.metrics = metrics
        self.backend = backend
        self._programs: dict = {}   # (key, plane) -> catalog row
        self._pending: dict = {}    # (key, plane) -> build-time fields
        self._chunks: dict = {}     # key -> {count, sum, min, max}
        self._write_errors = 0
        self._mu = threading.Lock()

    # ------------------------------------------------------------- write

    def now(self) -> float:
        return self.clock()

    def record_build(self, spec, plane, cfg, build_wall_s: float):
        """Stage one build's host-side facts (called by the registry
        at `_build` time, when the protocol config — the cost-model
        input — is in hand).  The row itself is appended by
        `record_program` once the first launch supplies the
        compile-side facts."""
        pend = {"key": spec.compile_key(), "plane": plane,
                "protocol": spec.protocol, "engine": spec.engine,
                "chunk_ms": spec.chunk_ms, "superstep": spec.superstep,
                "build_wall_s": round(float(build_wall_s), 6),
                "predicted": cost_model_predictions(cfg,
                                                    spec.route_kernel)}
        with self._mu:
            self._pending[(pend["key"], plane)] = pend

    def record_program(self, key: str, plane, *, lower_wall_s: float,
                       compile_wall_s: float, memory: dict, cost: dict,
                       arg_leaves=None, batch=None) -> dict | None:
        """Append THE catalog row for one compiled program, joining
        the staged build facts with the capture's compile facts.
        Idempotent per (key, plane): a duplicate capture (abandoned
        watchdog thread racing its retry) records nothing."""
        backend = self.backend or jax.default_backend()
        with self._mu:
            if (key, plane) in self._programs:
                return None
            pend = self._pending.pop((key, plane), None) or {}
            row = {"schema": SCHEMA, "kind": "program", "key": key,
                   "plane": plane, "backend": backend,
                   "lower_wall_s": round(float(lower_wall_s), 6),
                   "compile_wall_s": round(float(compile_wall_s), 6),
                   "memory": dict(memory), "cost": dict(cost)}
            for field in ("protocol", "engine", "chunk_ms", "superstep",
                          "build_wall_s", "predicted"):
                if field in pend:
                    row[field] = pend[field]
            if arg_leaves is not None:
                row["arg_leaves"] = int(arg_leaves)
            if batch is not None:
                row["batch"] = int(batch)
            self._programs[(key, plane)] = row
        if self.path is not None:
            try:
                jsonl.append_line(self.path, row, fsync=self.fsync)
            except OSError as e:
                # in-memory catalog keeps the row; the durable log
                # degrades loudly (the spans.py convention)
                with self._mu:
                    self._write_errors += 1
                print(f"programs: append to {self.path} failed ({e}); "
                      "row kept in memory only", file=sys.stderr)
        return row

    def observe_chunk(self, key: str, wall_s: float, lanes=None):
        """One launched chunk's wall seconds for compile key `key`
        (all planes — the scheduler's chunk covers the primary and its
        shadow passes).  Aggregates in memory; feeds the attached
        metrics registry's histogram when one is on."""
        w = float(wall_s)
        with self._mu:
            agg = self._chunks.get(key)
            if agg is None:
                agg = {"count": 0, "sum": 0.0, "min": w, "max": w}
                self._chunks[key] = agg
            agg["count"] += 1
            agg["sum"] += w
            agg["min"] = min(agg["min"], w)
            agg["max"] = max(agg["max"], w)
        if self.metrics is not None:
            self.metrics.observe("wtpu_program_chunk_seconds", w)

    # -------------------------------------------------------------- read

    def programs(self) -> list:
        """The recorded rows, insertion-ordered."""
        with self._mu:
            return list(self._programs.values())

    def chunk_stats(self) -> dict:
        """Per-compile-key chunk-wall aggregates (copies)."""
        with self._mu:
            return {k: dict(v) for k, v in self._chunks.items()}

    def stats(self) -> dict:
        with self._mu:
            return {"programs": len(self._programs),
                    "pending_builds": len(self._pending),
                    "chunk_keys": len(self._chunks),
                    "write_errors": self._write_errors,
                    "durable": self.path is not None}

    def drift(self) -> list:
        """Predicted-vs-measured per program (module docstring):
        ``vmem_ratio`` = measured temp bytes / predicted route VMEM
        bytes (>1: the model under-predicts the executable's working
        set), plus the measured mean chunk wall and — when XLA's cost
        analysis is available — the implied flops/s."""
        return drift_rows(self.programs(), self.chunk_stats())

    def report(self) -> dict:
        """The ``GET /w/batch/programs`` body: the program table, the
        top compile-wall consumers, the drift pass and the catalog's
        own health."""
        out = summarize_programs(self.programs(), self.chunk_stats())
        out["catalog"] = self.stats()
        if self.path is not None:
            out["catalog"]["path"] = self.path
        return out


# ------------------------------------------------------------ reporting

def drift_rows(rows, chunks=None) -> list:
    """The drift pass over catalog rows (shared by the live catalog
    and `tools/programs.py` reading JSONL files)."""
    chunks = chunks or {}
    out = []
    for row in rows:
        pred = (row.get("predicted") or {}).get("route_vmem_bytes")
        temp = (row.get("memory") or {}).get("temp_bytes")
        d = {"key": row.get("key"), "plane": row.get("plane"),
             "backend": row.get("backend"),
             "route_kernel": (row.get("predicted") or {})
             .get("route_kernel")}
        if pred and temp is not None:
            d["predicted_vmem_bytes"] = pred
            d["measured_temp_bytes"] = temp
            d["vmem_ratio"] = round(temp / pred, 4)
        agg = chunks.get(row.get("key"))
        if agg and agg["count"]:
            mean = agg["sum"] / agg["count"]
            d["chunk_wall_mean_s"] = round(mean, 6)
            d["chunks"] = agg["count"]
            flops = (row.get("cost") or {}).get("flops")
            if flops and mean > 0:
                d["measured_flops_per_s"] = round(flops / mean, 1)
        out.append(d)
    return out


def summarize_programs(rows, chunks=None) -> dict:
    """One report dict from catalog rows: the bytes-per-program table
    (compile-wall sorted), the top compile-wall consumers, and the
    drift outliers (|log ratio| sorted — a 4x under-prediction and a
    4x over-prediction are equally interesting)."""
    import math
    table = sorted(rows, key=lambda r: -(r.get("compile_wall_s") or 0))
    top = [{"key": r.get("key"), "plane": r.get("plane"),
            "compile_wall_s": r.get("compile_wall_s")}
           for r in table[:3]]
    dr = drift_rows(rows, chunks)
    outliers = sorted(
        (d for d in dr if d.get("vmem_ratio")),
        key=lambda d: -abs(math.log(max(d["vmem_ratio"], 1e-12))))
    return {"programs": table,
            "count": len(table),
            "compile_wall_total_s": round(
                sum(r.get("compile_wall_s") or 0 for r in rows), 6),
            "top_compile": top,
            "drift": dr,
            "drift_outliers": outliers[:5]}


def read_catalog(path) -> list:
    """Parse one catalog JSONL (torn tail tolerated — the
    `utils/jsonl.iter_lines` contract).  Rows that are not
    program-shaped are skipped with a stderr note, like
    `read_spans`."""
    out = []
    for i, row in jsonl.iter_lines(path, label="programs"):
        if not isinstance(row, dict) or "key" not in row \
                or "compile_wall_s" not in row:
            print(f"programs: row {i} of {path} is not a program row "
                  "(no key/compile_wall_s); skipped", file=sys.stderr)
            continue
        out.append(row)
    return out


# ---------------------------------------------------------- projection

def _series(name: str, **labels) -> str:
    """A label-styled series name (`parse_exposition` keys on the
    full ``name{labels}`` string).  Only used for gauges — histogram
    names must stay bare (the exposition appends its own ``_bucket``
    label suffix)."""
    inner = ",".join(f'{k}="{v}"' for k, v in labels.items())
    return f"{name}{{{inner}}}"


def refresh_catalog_metrics(metrics, catalog) -> None:
    """Project a catalog into a `MetricsRegistry` at scrape time (the
    serve/instrument.py projection convention: the catalog keeps the
    source of truth; scrape-time `set_counter`/`set_gauge` keeps the
    exposed series monotone where the source is)."""
    rows = catalog.programs()
    chunks = catalog.chunk_stats()
    metrics.set_gauge("wtpu_programs_cataloged", len(rows))
    total = 0.0
    for row in rows:
        key = row.get("key")
        plane = row.get("plane") or "none"
        labels = {"key": key, "plane": plane}
        cw = row.get("compile_wall_s") or 0.0
        total += cw
        metrics.set_gauge(_series("wtpu_program_compile_seconds",
                                  **labels), cw)
        mem = row.get("memory") or {}
        for field in ("temp_bytes", "argument_bytes", "output_bytes",
                      "code_bytes"):
            if field in mem:
                metrics.set_gauge(
                    _series(f"wtpu_program_{field}", **labels),
                    mem[field])
        flops = (row.get("cost") or {}).get("flops")
        if flops is not None:
            metrics.set_gauge(_series("wtpu_program_flops", **labels),
                              flops)
    metrics.set_gauge("wtpu_program_compile_wall_total_seconds",
                      round(total, 6))
    for d in drift_rows(rows, chunks):
        if d.get("vmem_ratio") is not None:
            metrics.set_gauge(
                _series("wtpu_costmodel_drift", key=d["key"],
                        plane=d["plane"] or "none"),
                d["vmem_ratio"])
    for key, agg in chunks.items():
        metrics.set_counter(
            _series("wtpu_program_chunks_total", key=key),
            agg["count"])
        if agg["count"]:
            metrics.set_gauge(
                _series("wtpu_program_chunk_wall_mean_seconds",
                        key=key),
                round(agg["sum"] / agg["count"], 6))
