"""On-device metrics plane — zero-host-sync engine telemetry.

The reference's observability surface is host-side and per-event
(`StatsHelper` min/max/avg over live nodes, `ProgressPerTime` per-round
time series — SURVEY.md §2.3, §5.5).  Inside a compiled superstep chunk
neither exists: `utils/profiling.run_report` reads final-state counters
only AFTER the chunk returns, so everything that happens *during* a
10k-ms scan is invisible.  This package adds the missing plane:

  device side (`plane`, `engine`): a `MetricsSpec(stat_each_ms,
  counters)` compiles an interval recorder into the engine chunk —
  fixed-shape ``[T, K]`` int32 series carried alongside the simulation
  state and updated with pure on-device reductions (no host callbacks,
  no `device_get` mid-scan — the `host_sync` lint runs over the
  instrumented builds too, analysis/targets.py `+metrics` targets);

  host side (`export`): a `MetricsFrame` wraps the fetched series and
  exports (a) a ProgressPerTime-style CSV via `tools/csvf`, (b) a
  Chrome-trace/Perfetto JSON that loads on one timeline with the XLA
  op traces of a `jax.profiler` capture, and (c) the structured
  ``engine_metrics`` block `bench.py` embeds in its JSON line.

Two hard invariants (tests/test_obs.py, analysis `metrics_zero_cost`):

  * metrics-ON is simulation-bit-identical: the recorder only READS the
    carried state (`counter_values` is a pure function of it), so the
    `NetState`/`pstate` trajectory equals the uninstrumented engine's
    for every covered protocol and engine variant;
  * metrics-OFF has zero residue: the uninstrumented builders never
    import this package, and the `metrics_zero_cost` lint pins their
    scan-carry width and jaxpr op count so the plane can never silently
    tax the hot path.

The EVENT plane (`trace`, `decode`, `diff` — PR 5) answers the
question the metrics plane cannot: "which message, when, to whom".  A
`TraceSpec(capacity, events, node_filter)` compiles a fixed-shape
``[cap, 6]`` int32 event ring into the engine chunk through the
`step_ms`/`step_kms` tap hook (per-ms exact inside fused K windows),
under the SAME two-sided contract (trace-ON bit-identical,
tests/test_trace.py; trace-OFF zero residue, analysis
`trace_zero_cost`).  On top of it `obs/diff.py` + `tools/divergence.py`
bisect the first state divergence between any two engine-variant
configurations down to the exact (ms, pytree leaf, element) and print
the decoded trace window around it from both runs.

The AUDIT plane (`audit`, `audit_report` — PR 6) closes the loop from
*describing* a run to *proving* it: an `AuditSpec(invariants, mode)`
compiles conservation-law monitors (message conservation, ring/spill
bounds, clock and done/counter monotonicity, broadcast-table
consistency, cross-shard exchange conservation) into every engine
variant through the same tap-hook chain, under the same two-sided
contract (audit-ON bit-identical, tests/test_audit.py; audit-OFF zero
residue, analysis `audit_zero_cost`).  `obs/ledger.py` appends a
`RunManifest` provenance row per bench run under ``reports/ledger/``,
and `tools/audit.py` is the one-command clean/violated CLI.

The HOST plane (`spans`, `metrics` — PR 18) covers the half the
device planes cannot see: admission, queueing, compile, launch /
retry / degrade, preemption, lease claims, crash replay.
`SpanRecorder` is the wall-clock flight recorder (bounded ring +
optional durable JSONL), `MetricsRegistry` the scrapeable Prometheus
mirror behind ``GET /w/batch/metrics``, and
`export.spans_to_perfetto` merges host spans with the device lanes
onto one Perfetto timeline (`tools/timeline.py`).
"""

from .audit import (AuditCarry, AuditSpec, INVARIANTS,  # noqa: F401
                    fast_forward_chunk_audit, init_audit,
                    scan_chunk_audit, scan_chunk_batched_audit)
from .audit_report import (AuditReport, audit_block,  # noqa: F401
                           audit_variant, cross_check_metrics)
from .decode import TraceFrame, trace_block  # noqa: F401
from .engine import (fast_forward_chunk_batched_metrics,  # noqa: F401
                     fast_forward_chunk_metrics, scan_chunk_batched_metrics,
                     scan_chunk_metrics, step_ms_metrics)
from .export import (MetricsFrame, engine_metrics_block,  # noqa: F401
                     spans_to_perfetto, to_perfetto, to_progress_csv,
                     trace_to_perfetto)
from .metrics import MetricsRegistry, parse_exposition  # noqa: F401
from .plane import MetricsCarry, counter_values, init_metrics  # noqa: F401
from .spans import SpanRecorder, read_spans  # noqa: F401
from .spec import COUNTERS, MetricsSpec  # noqa: F401
from .trace import (EVENTS, TraceCarry, TraceSpec,  # noqa: F401
                    fast_forward_chunk_trace, init_trace,
                    scan_chunk_batched_trace, scan_chunk_trace,
                    step_ms_trace, trace_jump, trace_tap)
