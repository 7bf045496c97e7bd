"""Simulation-as-a-service: the batch-workload request plane.

The reference ships a wserver REST façade for ONE interactive network
(`server/` mirrors it).  This package is its batch analogue — ROADMAP
item 2's "millions of users" path: many concurrent scenario requests,
coalesced into few compiled device programs.

  `spec`      — `ScenarioSpec`: the frozen, serializable description of
                one scenario run (protocol, params, engine variant,
                superstep K, obs planes, attack/partition, seeds) with
                a canonical JSON form, a `compile_key()` digest over
                exactly the program-affecting subset, and validation
                that reuses the engine's own eligibility gates
                (`check_chunk_config`/`pick_superstep`) so a bad spec
                is refused with remedy text instead of compiled.
  `registry`  — `CompileRegistry`: compile-key -> jitted-chunk-program
                registry layered on the PR-2 persistent compile cache;
                repeat shapes are warm starts, hit/miss counters ride
                the obs block conventions.
  `scheduler` — `Scheduler`: a coalescing queue that groups pending
                requests sharing a compile key and runs them as ONE
                vmapped seed-batched program (continuous seed batching:
                compatible requests join at the next chunk boundary),
                returning per-request ProgressPerTime/trace/audit
                artifacts and appending one `RunManifest` ledger row
                per request.  Since PR 13 it is multi-tenant: bounded
                per-tenant admission (`AdmissionError` -> HTTP 429 +
                retry-after), deficit-round-robin fairness over
                tenants (`TenantPolicy` weights), and chunk-boundary
                checkpoint-preemption with bit-identical resumption.
  `service`   — `Service`: submit/status/result surface (in-process
                and behind `server/http.py`'s `/w/batch/*` routes)
                streaming progress from the on-device metrics plane.
  `journal`   — `SubmissionJournal` (PR 15): the durable submission
                WAL behind `Scheduler(journal_dir=)` — accepted
                submits fsync'd before ack, tombstoned on settle,
                replayed by `resume_journal()`/`recover()`; with the
                poison-lane quarantine and hung-launch watchdog it
                makes serve crash-only (scheduler module docstring).
                Since PR 17 it also holds `LeaseTable`: append-only
                fsync'd work claims with deadlines — the fleet's
                partition of one shared journal across N workers.
  `fleet`     — `FleetWorker`/`spawn_worker` (PR 17): lease-based
                multi-process scale-out over the crash-only substrate
                — N worker processes share one journal/ledger/
                checkpoint directory, a dead worker's leases expire
                and any survivor replays or checkpoint-adopts its
                work, and the PR-13 ledger join dedups across workers.
                `FleetService` (service.py) is the thin front tier
                behind the same `/w/batch/*` routes.
  `instrument`— `Instrumentation` (PR 18): the host-plane flight
                recorder + metrics handle — `Scheduler(instrument=)` /
                `FleetWorker(instrument=)` thread request-lifecycle
                wall-clock spans (obs/spans.py) and the scrapeable
                Prometheus registry (obs/metrics.py, served at
                ``GET /w/batch/metrics``) through the whole serve
                plane; OFF (the default None) costs a single is-None
                branch per site.
"""

from .fleet import (FleetWorker, check_fleet_size, fleet_paths,  # noqa: F401
                    spawn_worker, spawn_workers)
from .instrument import Instrumentation  # noqa: F401
from .journal import LeaseTable, SubmissionJournal  # noqa: F401
from .registry import CompileRegistry  # noqa: F401
from .scheduler import (AdmissionError, ForkState, Request,  # noqa: F401
                        Scheduler, StaleCheckpointError, TenantPolicy,
                        WatchdogTimeout)
from .service import FleetService, Service  # noqa: F401
from .spec import ENGINES, OBS_PLANES, ScenarioSpec  # noqa: F401
