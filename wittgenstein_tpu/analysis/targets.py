"""Analysis targets: one pinned small-CPU compile per registered protocol.

A target wraps everything a rule can look at — the protocol instance,
the example batched args, the jaxpr, and the optimized HLO of the
compiled superstep — computed lazily so source-only rules never pay for
a compile.  Configs are PINNED (node counts, seeds, chunk, ring sizing):
the carry/copy budgets in budgets.json are measured at exactly these
shapes, so a config change here is a budget change and must be reviewed
as one.

Engine selection mirrors the bench/harness dispatch: protocols eligible
for the batched seed-folded engine (spill-free, broadcast-free,
superstep-ok — core/batched.py) compile through `scan_chunk_batched`,
everything else through the vmapped `scan_chunk`.  That way the audited
program IS the shape of the program the drivers run, per protocol.
"""

from __future__ import annotations

import dataclasses
import functools

SEEDS = 2       # batched seed axis of every target
CHUNK = 8       # even, small: one scan, no phase-specialized unroll


def _enable_compile_cache():
    """The persistent XLA compile cache (repo-local, gitignored) — the
    same setup tests/conftest.py uses, via the ONE shared helper
    (core/harness.enable_persistent_cache); analysis runs are
    compile-bound on one core and every rerun after the first is ~free.
    The test/analysis cache stays at .jax_cache (conftest's location,
    so CLI and pytest analysis runs share entries); the bench/harness
    production cache lives under reports/jax_cache/."""
    import pathlib

    from ..core.harness import enable_persistent_cache

    cache = pathlib.Path(__file__).resolve().parent.parent.parent \
        / ".jax_cache"
    enable_persistent_cache(str(cache))


def leaf_shape_names(args) -> dict[str, set]:
    """HLO shape string -> candidate state leaf names, for attributing
    copies/DUS back to NetState / protocol-state fields (moved from
    tools/carry_audit.py)."""
    import collections

    names = collections.defaultdict(set)

    def walk(prefix, obj):
        if dataclasses.is_dataclass(obj):
            for f in dataclasses.fields(obj):
                walk(f"{prefix}.{f.name}" if prefix else f.name,
                     getattr(obj, f.name))
        elif isinstance(obj, (tuple, list)):
            for i, x in enumerate(obj):
                walk(f"{prefix}[{i}]", x)
        elif hasattr(obj, "shape"):
            dt = str(obj.dtype)
            dt = {"float32": "f32", "float64": "f64", "int32": "s32",
                  "int64": "s64", "uint32": "u32", "uint64": "u64",
                  "bool": "pred", "int8": "s8", "uint8": "u8",
                  "int16": "s16", "uint16": "u16"}.get(dt, dt)
            dims = ",".join(str(d) for d in obj.shape)
            names[f"{dt}[{dims}]"].add(prefix)

    walk("", args)
    return dict(names)


class AnalysisTarget:
    """Lazy compile artifacts for one protocol (or one bare function).

    Attributes the rules use:
      name          — registry name
      protocol      — the instance (None for `from_fn` targets)
      args          — example (net, pstate) batch, the scan carry
      jaxpr         — ClosedJaxpr of the superstep chunk
      hlo_text      — post-optimization HLO text (CPU backend)
      leaf_names    — shape string -> state field names
      engine        — "batched" | "vmapped" | "fn"
    """

    def __init__(self, name, build_fn, protocol=None, engine="fn"):
        self.name = name
        self.protocol = protocol
        self.engine = engine
        self._build_fn = build_fn       # () -> (callable, args)
        self._built = None

    @classmethod
    def from_protocol(cls, name, proto_fn, seeds=SEEDS, chunk=CHUNK):
        """Build from a zero-arg protocol factory; engine dispatch as in
        bench/harness (batched when eligible, else vmapped scan)."""

        def build():
            import jax
            import jax.numpy as jnp

            from ..core.batched import scan_chunk_batched
            from ..core.network import scan_chunk

            proto = proto_fn()
            # Eligibility is scan_chunk_batched's own guard — one source
            # of truth; ineligible protocols audit the vmapped engine
            # the drivers would actually run for them.
            try:
                base = scan_chunk_batched(proto, chunk, t0_mod=None)
                engine = "batched"
            except ValueError:
                base = jax.vmap(scan_chunk(proto, chunk, superstep=1))
                engine = "vmapped"
            args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
            return base, args, proto, engine

        t = cls(name, None)
        t._build_fn = build
        return t

    @classmethod
    def from_fn(cls, name, fn, args):
        """Wrap an arbitrary ``fn(*args)`` (test fixtures, one-off
        audits).  `args` is the example input pytree."""
        return cls(name, lambda: (fn, args, None, "fn"))

    def _ensure_built(self):
        if self._built is None:
            _enable_compile_cache()
            fn, args, proto, engine = self._build_fn()
            self.protocol = proto if proto is not None else self.protocol
            self.engine = engine
            self._built = (fn, args)
        return self._built

    @functools.cached_property
    def args(self):
        return self._ensure_built()[1]

    @functools.cached_property
    def jaxpr(self):
        import jax

        fn, args = self._ensure_built()
        return jax.make_jaxpr(fn)(*args)

    @functools.cached_property
    def hlo_text(self) -> str:
        import jax

        fn, args = self._ensure_built()
        return jax.jit(fn).lower(*args).compile().as_text()

    @functools.cached_property
    def leaf_names(self):
        return leaf_shape_names(self.args)


def _handel(n=64, seeds=SEEDS, chunk=CHUNK, **kw):
    from ..models.handel import Handel

    down = n // 10
    params = dict(node_count=n, threshold=int(0.99 * (n - down)),
                  nodes_down=down, pairing_time=4, level_wait_time=50,
                  dissemination_period_ms=20, fast_path=10,
                  horizon=64, inbox_cap=12)
    params.update(kw)
    return Handel(**params)


def handel_audit_target(n=256, seeds=2, chunk=40,
                        plane_barrier=True) -> AnalysisTarget:
    """The tools/carry_audit.py build, at its historical defaults: the
    exact bench program (batched Handel, phase-specialized when the
    chunk aligns), with the plane-barrier A/B knob."""

    def build():
        import jax
        import jax.numpy as jnp

        from ..core.batched import scan_chunk_batched

        proto = _handel(n=n)
        lcm = getattr(proto, "schedule_lcm", None)
        t0 = 0 if (lcm and chunk % lcm == 0) else None
        base = scan_chunk_batched(proto, chunk, t0_mod=t0,
                                  plane_barrier=plane_barrier)
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return base, args, proto, "batched"

    t = AnalysisTarget(f"Handel[n={n},audit]", None)
    t._build_fn = build
    return t


#: The request plane's phase-specialized batched Handel program (the
#: compact `core/network.phase_plan` form `CompileRegistry` builds when
#: the chunk is a multiple of the schedule lcm), audited under its own
#: name: its lead superstep and its inner rest scan are separate while
#: bodies, each gated like the plain program's one (carry_copy's
#: per-body metrics).  Two lcm blocks.
PHASE_TARGET = "Handel+phase"
_PHASE_CHUNK = 40


def _phase_target(name: str, seeds=SEEDS) -> AnalysisTarget:
    def build():
        import jax
        import jax.numpy as jnp

        from ..core.batched import scan_chunk_batched

        proto = _handel()
        base = scan_chunk_batched(proto, _PHASE_CHUNK, t0_mod=0)
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return base, args, proto, "batched+phase"

    t = AnalysisTarget(name, None)
    t._build_fn = build
    t.ms_per_iter = 4       # one superstep in each of the two bodies
    return t


def _registry() -> dict:
    """name -> zero-arg protocol factory at the pinned analysis config.
    Every entry must init + compile on CPU in seconds at these shapes."""
    from ..models.avalanche import Slush, Snowflake
    from ..models.casper import CasperIMD
    from ..models.dfinity import Dfinity
    from ..models.enr import ENRGossiping
    from ..models.gsf import GSFSignature
    from ..models.handel_cardinal import HandelCardinal
    from ..models.handeleth2 import HandelEth2
    from ..models.optimistic import OptimisticP2PSignature
    from ..models.p2pflood import P2PFlood
    from ..models.paxos import Paxos
    from ..models.pingpong import PingPong
    from ..models.sanfermin import SanFermin

    return {
        "Handel": _handel,
        "HandelCardinal": lambda: HandelCardinal(
            node_count=64, nodes_down=6, threshold=57, pairing_time=4,
            dissemination_period_ms=20, fast_path=10),
        "GSFSignature": lambda: GSFSignature(node_count=64),
        "HandelEth2": lambda: HandelEth2(node_count=64),
        "PingPong": lambda: PingPong(node_count=64),
        "P2PFlood": lambda: P2PFlood(
            node_count=64, dead_node_count=6, peers_count=8,
            delay_before_resent=1, delay_between_sends=1),
        "Slush": lambda: Slush(node_count=64, rounds=4, k=5),
        "Snowflake": lambda: Snowflake(node_count=64, k=5, beta=3),
        "Paxos": lambda: Paxos(acceptor_count=3, proposer_count=3,
                               timeout=1000),
        "OptimisticP2PSignature": lambda: OptimisticP2PSignature(
            node_count=64, threshold=33, connection_count=13,
            pairing_time=3),
        "SanFermin": lambda: SanFermin(node_count=64),
        "Dfinity": lambda: Dfinity(block_producers_count=10,
                                   attesters_count=10,
                                   attesters_per_round=10),
        "CasperIMD": lambda: CasperIMD(
            cycle_length=4, block_producers_count=2,
            attesters_per_round=10, tick_ms=40),
        "ENRGossiping": lambda: ENRGossiping(
            nodes=40, total_peers=5, max_peers=12,
            number_of_different_capabilities=5, cap_per_node=2,
            cap_gossip_time=500, time_to_change=5_000,
            time_to_leave=20_000, changing_nodes=0.4),
    }


#: Protocols whose quiet-window fast-forward build (the `lax.while_loop`
#: engine of core/network.fast_forward_chunk) is audited alongside the
#: dense scan: the four bit-identity-tested opt-ins.  The while body is
#: a different compiled program — its copies, dtypes and host-sync
#: profile are gated separately under the "<name>+ff" target names.
FF_PROTOCOLS = ("Handel", "PingPong", "P2PFlood", "Dfinity")

FF_SUFFIX = "+ff"

#: Protocols whose metrics-ON builds (wittgenstein_tpu/obs) are audited
#: alongside the uninstrumented engines: the instrumented chunk is a
#: different compiled program — its host-sync profile, carry copies and
#: carry width are gated separately under "<name>+metrics" (dense
#: recorder; batched seed-folded when eligible, mirroring the obs
#: engine dispatch) and "<name>+ffmetrics" (instrumented quiet-window
#: while loop).  The `metrics_zero_cost` rule additionally asserts the
#: plane is actually LIVE in these builds (carry widens by the
#: MetricsCarry leaves) and has zero residue everywhere else.
METRICS_PROTOCOLS = ("Handel", "PingPong", "Dfinity")
METRICS_SUFFIX = "+metrics"
FFM_PROTOCOLS = ("PingPong",)
FFM_SUFFIX = "+ffmetrics"

#: pinned instrumentation for the metrics targets: even interval (the
#: batched fused-pair engine requires it), 4 rows over the CHUNK=8 ms.
_METRICS_EACH_MS = 2

#: Protocols whose flight-recorder builds (obs/trace.py) are audited
#: alongside the uninstrumented engines under "<name>+trace": the
#: traced chunk is a different compiled program — its host-sync
#: profile, carry copies and carry width are gated separately, and the
#: `trace_zero_cost` rule asserts the recorder is actually LIVE there
#: (carry widens by the TraceCarry leaves) while every OTHER target's
#: carry width proves trace-OFF zero residue.  One broadcast protocol
#: (PingPong — exercises the bc-deliver/retire observation) and the
#: flagship (Handel).
TRACE_PROTOCOLS = ("PingPong", "Handel")
TRACE_SUFFIX = "+trace"

#: pinned ring capacity for the trace targets: small (the rule checks
#: structure, not volume) but big enough that the CHUNK=8 window never
#: truncates.
_TRACE_CAP = 256


def _trace_target(name: str, seeds=SEEDS, chunk=CHUNK) -> AnalysisTarget:
    base_name = name[:-len(TRACE_SUFFIX)]

    def build():
        import jax
        import jax.numpy as jnp

        from ..obs.trace import TraceSpec, scan_chunk_trace

        proto = _registry()[base_name]()
        spec = TraceSpec(capacity=_TRACE_CAP)
        base = jax.vmap(scan_chunk_trace(proto, chunk, spec))
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return base, args, proto, "vmapped+trace"

    t = AnalysisTarget(name, None)
    t._build_fn = build
    return t


#: Protocols whose invariant-audit builds (obs/audit.py) are audited
#: alongside the uninstrumented engines under "<name>+audit": the
#: audited chunk is a different compiled program — its host-sync
#: profile, carry copies and carry width are gated separately, and the
#: `audit_zero_cost` rule asserts the monitors are actually LIVE there
#: (carry widens by the AuditCarry leaves) while every OTHER target's
#: carry width proves audit-OFF zero residue.  One broadcast protocol
#: (PingPong — exercises the bc_consistency monitor) and the flagship
#: (Handel — ring conservation under real traffic).
AUDIT_PROTOCOLS = ("PingPong", "Handel")
AUDIT_SUFFIX = "+audit"


def _audit_target(name: str, seeds=SEEDS, chunk=CHUNK) -> AnalysisTarget:
    base_name = name[:-len(AUDIT_SUFFIX)]

    def build():
        import jax
        import jax.numpy as jnp

        from ..obs.audit import AuditSpec, scan_chunk_audit

        proto = _registry()[base_name]()
        spec = AuditSpec()
        base = jax.vmap(scan_chunk_audit(proto, chunk, spec))
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return base, args, proto, "vmapped+audit"

    t = AnalysisTarget(name, None)
    t._build_fn = build
    return t


#: The chaos-plane build (wittgenstein_tpu/chaos) audited under
#: "<name>+chaos": the `ChaosProtocol` wrap is a different compiled
#: program — the window-entry fault application and the per-ms outbox
#: adversaries (loss draw + delay inflation) ride the scan body — so
#: its host-sync profile, carry copies and carry width are gated
#: separately, while every OTHER target's pinned carry width proves
#: the chaos-OFF engine carries zero residue (the engine hook is a
#: python-level getattr, never traced).  PingPong: broadcast protocol
#: (partition state feeds the per-ms bc recompute) and the
#: fast-forward clamp's main consumer.
CHAOS_PROTOCOLS = ("PingPong",)
CHAOS_SUFFIX = "+chaos"


def _chaos_target(name: str, seeds=SEEDS, chunk=CHUNK) -> AnalysisTarget:
    base_name = name[:-len(CHAOS_SUFFIX)]

    def build():
        import jax
        import jax.numpy as jnp

        from ..chaos import ChaosProtocol, FaultSchedule
        from ..core.network import scan_chunk

        inner = _registry()[base_name]()
        n = inner.cfg.n
        # every fault class live inside the CHUNK=8 ms window, all
        # transitions even (superstep-2-compatible shape)
        proto = ChaosProtocol(inner, FaultSchedule(
            churn=((1, 2, 6),),
            partitions=((2, 6, 1, 0, max(1, n // 2)),),
            loss=((0, chunk, 250, 0, n, 0, n),),
            delay=((2, 6, 1, 0, n, 0, n),)))
        base = jax.vmap(scan_chunk(proto, chunk))
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return base, args, proto, "vmapped+chaos"

    t = AnalysisTarget(name, None)
    t._build_fn = build
    return t


#: The matrix-driver build (wittgenstein_tpu/matrix) audited under
#: "<name>+matrix": one cell of a pinned SweepGrid expanded through
#: the grid/spec path and compiled exactly the way the serve registry
#: compiles it for the scheduler (vmapped scan_chunk of the cell's
#: built protocol).  The matrix layer is host-side planning — the
#: zero-cost rules (carry_extra_leaves=0, transfer_ops=0) prove the
#: driver adds NO compiled residue over the plain engine, and the
#: cell's latency axis pins compiled coverage of the per-link
#: heterogeneous/asymmetric model (core/latency.py).
MATRIX_PROTOCOLS = ("PingPong",)
MATRIX_SUFFIX = "+matrix"

#: the pinned matrix-target cell's latency axis value (the PR-12
#: heterogeneous model: base 4, +spread 3, +skew 2, seed 1)
_MATRIX_LATENCY = "NetworkHeterogeneousLatency(4,3,2,1)"


def _matrix_target(name: str, seeds=SEEDS, chunk=CHUNK) -> AnalysisTarget:
    base_name = name[:-len(MATRIX_SUFFIX)]

    def build():
        import jax
        import jax.numpy as jnp

        from ..core.network import scan_chunk
        from ..matrix import SweepGrid

        grid = SweepGrid(
            name="analysis",
            base={"protocol": base_name,
                  "params": {"node_count": 64},
                  "seeds": [0], "sim_ms": chunk, "chunk_ms": chunk,
                  "obs": []},
            axes=({"name": "lat", "field": "latency_model",
                   "values": [_MATRIX_LATENCY, None]},))
        cell = grid.expand()[0]
        spec = cell.spec.validate()
        proto = spec.build_protocol()
        base = jax.vmap(scan_chunk(proto, chunk,
                                   superstep=spec.superstep))
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return base, args, proto, "vmapped+matrix"

    t = AnalysisTarget(name, None)
    t._build_fn = build
    return t


#: The tenancy build (PR 13) audited under "<name>+tenancy": the SAME
#: grid/spec compile path as the matrix target but with the schema-4
#: tenancy trio (tenant/priority/deadline_ms) set — scheduling
#: metadata that must stay scheduler-side.  The zero-cost rules
#: (carry_extra_leaves=0, transfer_ops=0) prove the tenancy plane adds
#: NO compiled residue: a tenancy-labelled spec compiles the identical
#: program its unlabelled twin does (the fields are digest-only, never
#: compile-key — serve/spec.py schema-4 note).
TENANCY_PROTOCOLS = ("PingPong",)
TENANCY_SUFFIX = "+tenancy"


def _tenancy_target(name: str, seeds=SEEDS, chunk=CHUNK) -> AnalysisTarget:
    base_name = name[:-len(TENANCY_SUFFIX)]

    def build():
        import jax
        import jax.numpy as jnp

        from ..core.network import scan_chunk
        from ..serve.spec import ScenarioSpec

        spec = ScenarioSpec(
            protocol=base_name, params={"node_count": 64},
            seeds=(0,), sim_ms=chunk, chunk_ms=chunk, obs=(),
            tenant="analysis", priority=3,
            deadline_ms=60_000).validate()
        # the tenancy fields must not have split the compile key
        bare = ScenarioSpec(
            protocol=base_name, params={"node_count": 64},
            seeds=(0,), sim_ms=chunk, chunk_ms=chunk,
            obs=()).validate()
        assert spec.compile_key() == bare.compile_key(), \
            "tenancy fields leaked into the compile key"
        proto = spec.build_protocol()
        base = jax.vmap(scan_chunk(proto, chunk,
                                   superstep=spec.superstep))
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return base, args, proto, "vmapped+tenancy"

    t = AnalysisTarget(name, None)
    t._build_fn = build
    return t


#: The journal build (PR 15 — crash-only serve) audited under
#: "<name>+journal": the SAME spec-path compile as the tenancy target,
#: submitted through a `Scheduler(journal_dir=)` whose WAL append runs
#: BEFORE the ack.  Journaling (and the whole crash-safety ladder:
#: quarantine bisection, watchdog deadlines) is host-side only — the
#: zero-cost rules (carry_extra_leaves=0, transfer_ops=0) prove the
#: compiled chunk program carries ZERO crash-safety residue, and the
#: build asserts the replay contract's static half: the journaled spec
#: JSON round-trips to the submitted spec's digest and compile key, so
#: a replay re-runs EXACTLY the accepted config.
JOURNAL_PROTOCOLS = ("PingPong",)
JOURNAL_SUFFIX = "+journal"


def _journal_target(name: str, seeds=SEEDS, chunk=CHUNK) -> AnalysisTarget:
    base_name = name[:-len(JOURNAL_SUFFIX)]

    def build():
        import tempfile

        import jax
        import jax.numpy as jnp

        from ..core.network import scan_chunk
        from ..serve import Scheduler
        from ..serve.spec import ScenarioSpec

        spec = ScenarioSpec(
            protocol=base_name, params={"node_count": 64},
            seeds=(0,), sim_ms=chunk, chunk_ms=chunk, obs=()).validate()
        with tempfile.TemporaryDirectory() as jd:
            sch = Scheduler(journal_dir=jd)
            sch.submit(spec)
            entries = sch.journal.replay()
            assert len(entries) == 1, entries
            stored = ScenarioSpec.from_json(entries[0]["spec"])
            # the replay contract: the WAL row IS the accepted config
            assert stored.digest() == spec.digest(), \
                "journaled spec does not round-trip to the submitted " \
                "digest (a replay would re-run a different config)"
            assert stored.validate().compile_key() == \
                spec.compile_key(), \
                "journaled spec resolves to a different compile key"
        proto = spec.build_protocol()
        base = jax.vmap(scan_chunk(proto, chunk,
                                   superstep=spec.superstep))
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return base, args, proto, "vmapped+journal"

    t = AnalysisTarget(name, None)
    t._build_fn = build
    return t


#: The memo build (PR 14 — wittgenstein_tpu/memo) audited under
#: "<name>+memo": the honest-prefix program a snapshot-fork campaign
#: runs, compiled through the same grid/spec path.  Memoization is
#: entirely HOST-side (prefix planning, state forks, lane freezing in
#: the scheduler): the zero-cost rules (carry_extra_leaves=0,
#: transfer_ops=0) prove that with memo OFF — and equally with it on —
#: the compiled chunk program carries NO memo residue, and the build
#: asserts the memo contract's two static halves: stripping post-fork
#: adversity lands exactly on the clean sibling's compile key, and the
#: adversity start the planner forks before is the schedule's first
#: window.
MEMO_PROTOCOLS = ("PingPong",)
MEMO_SUFFIX = "+memo"


def _memo_target(name: str, seeds=SEEDS, chunk=CHUNK) -> AnalysisTarget:
    base_name = name[:-len(MEMO_SUFFIX)]

    def build():
        import jax
        import jax.numpy as jnp

        from ..core.network import scan_chunk
        from ..memo import first_adversity_ms, strip_adversity
        from ..serve.spec import ScenarioSpec

        adverse = ScenarioSpec(
            protocol=base_name, params={"node_count": 64},
            seeds=(0,), sim_ms=2 * chunk, chunk_ms=chunk, obs=(),
            fault_schedule={"loss": [[chunk, 2 * chunk, 500,
                                      0, 64, 0, 64]]}).validate()
        clean = ScenarioSpec(
            protocol=base_name, params={"node_count": 64},
            seeds=(0,), sim_ms=2 * chunk, chunk_ms=chunk,
            obs=()).validate()
        prefix = strip_adversity(adverse)
        assert prefix.compile_key() == clean.compile_key(), \
            "stripping post-fork adversity must land on the clean " \
            "sibling's compile key (the fork-group sharing contract)"
        assert first_adversity_ms(adverse) == chunk
        proto = prefix.build_protocol()
        base = jax.vmap(scan_chunk(proto, chunk,
                                   superstep=prefix.superstep))
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return base, args, proto, "vmapped+memo"

    t = AnalysisTarget(name, None)
    t._build_fn = build
    return t


#: Superstep-K targets (PR 4): the fused K-ms window engine
#: (core/network.step_kms / batched twin) compiled at a pinned K on a
#: floor-rich latency model, so the `superstep_amortization` budgets pin
#: the amortized sort/scatter counts per simulated ms.  Dfinity
#: self-sends (committee addressing includes the sender), so its max
#: provable window is the universal K = 2; the no-self-send protocols
#: get K = 4 (CHUNK = 8 keeps one full window pair per scan body).
SS_PROTOCOLS = {
    "Handel+ss4": ("Handel", 4),
    "P2PFlood+ss4": ("P2PFlood", 4),
    "Dfinity+ss2": ("Dfinity", 2),
}

#: floor-rich latency override for the K > 2 targets (floor 8 >= K - 1)
_SS_LATENCY = "NetworkFixedLatency(8)"

#: Pallas-routing targets (PR 9): the SAME engine/K configs as the
#: superstep targets but with the fused routing megakernel ON
#: (ops/pallas_route.py, interpret mode on CPU) — the
#: `superstep_amortization` budgets then pin the headline claim:
#: compiled sort/scatter ops per simulated ms ~0 once the binning
#: lives inside the kernel.  The Handel exact target additionally
#: turns the delivery-merge/scoring Pallas kernels on
#: (pallas_merge=True) so every remaining per-ms sort is accounted:
#: the megakernel program is the all-Pallas one.  name -> (base, K,
#: all_pallas).
ROUTE_PROTOCOLS = {
    "Handel+pallas_route": ("Handel", 4, True),
    "HandelCardinal+pallas_route": ("HandelCardinal", 4, False),
    "P2PFlood+pallas_route": ("P2PFlood", 4, False),
}

ROUTE_SUFFIX = "+pallas_route"


def _route_target(name: str, seeds=SEEDS, chunk=CHUNK) -> AnalysisTarget:
    base_name, k, all_pallas = ROUTE_PROTOCOLS[name]

    def build():
        import jax
        import jax.numpy as jnp

        from ..core.batched import scan_chunk_batched
        from ..core.network import scan_chunk
        from ..ops.pallas_route import with_route

        if base_name == "Handel":
            kw = dict(network_latency_name=_SS_LATENCY)
            if all_pallas:
                kw["pallas_merge"] = True
            proto = _handel(**kw)
        elif base_name == "HandelCardinal":
            from ..models.handel_cardinal import HandelCardinal
            proto = HandelCardinal(
                node_count=64, nodes_down=6, threshold=57, pairing_time=4,
                dissemination_period_ms=20, fast_path=10,
                network_latency_name=_SS_LATENCY)
        else:
            from ..models.p2pflood import P2PFlood
            proto = P2PFlood(
                node_count=64, dead_node_count=6, peers_count=8,
                delay_before_resent=1, delay_between_sends=1,
                network_latency_name=_SS_LATENCY)
        try:
            base = scan_chunk_batched(proto, chunk, superstep=k)
            engine = f"batched+ss{k}+pallas_route"
        except ValueError:
            base = jax.vmap(scan_chunk(proto, chunk, superstep=k))
            engine = f"vmapped+ss{k}+pallas_route"
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return with_route(base, "pallas"), args, proto, engine

    t = AnalysisTarget(name, None)
    t._build_fn = build
    t.ms_per_iter = k
    return t


def _ss_target(name: str, seeds=SEEDS, chunk=CHUNK) -> AnalysisTarget:
    base_name, k = SS_PROTOCOLS[name]

    def build():
        import jax
        import jax.numpy as jnp

        from ..core.batched import scan_chunk_batched
        from ..core.network import scan_chunk

        if base_name == "Handel":
            proto = _handel(network_latency_name=_SS_LATENCY)
        elif base_name == "P2PFlood":
            from ..models.p2pflood import P2PFlood
            proto = P2PFlood(
                node_count=64, dead_node_count=6, peers_count=8,
                delay_before_resent=1, delay_between_sends=1,
                network_latency_name=_SS_LATENCY)
        else:
            proto = _registry()[base_name]()
        try:
            base = scan_chunk_batched(proto, chunk, superstep=k)
            engine = f"batched+ss{k}"
        except ValueError:
            base = jax.vmap(scan_chunk(proto, chunk, superstep=k))
            engine = f"vmapped+ss{k}"
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return base, args, proto, engine

    t = AnalysisTarget(name, None)
    t._build_fn = build
    t.ms_per_iter = k
    return t


def _metrics_target(name: str, seeds=SEEDS, chunk=CHUNK) -> AnalysisTarget:
    base_name = name[:-len(METRICS_SUFFIX)]

    def build():
        import jax
        import jax.numpy as jnp

        from ..obs import MetricsSpec
        from ..obs.engine import (scan_chunk_batched_metrics,
                                  scan_chunk_metrics)

        proto = _registry()[base_name]()
        spec = MetricsSpec(stat_each_ms=_METRICS_EACH_MS)
        try:
            base = scan_chunk_batched_metrics(proto, chunk, spec)
            engine = "batched+metrics"
        except ValueError:
            base = jax.vmap(scan_chunk_metrics(proto, chunk, spec))
            engine = "vmapped+metrics"
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return base, args, proto, engine

    t = AnalysisTarget(name, None)
    t._build_fn = build
    return t


def _ffm_target(name: str, seeds=SEEDS, chunk=CHUNK) -> AnalysisTarget:
    base_name = name[:-len(FFM_SUFFIX)]

    def build():
        import jax
        import jax.numpy as jnp

        from ..core.network import fast_forward_ok
        from ..obs import MetricsSpec
        from ..obs.engine import fast_forward_chunk_metrics

        proto = _registry()[base_name]()
        assert fast_forward_ok(proto), base_name
        spec = MetricsSpec(stat_each_ms=_METRICS_EACH_MS)
        base = fast_forward_chunk_metrics(proto, chunk, spec,
                                          seed_axis=True)
        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return base, args, proto, "fast_forward+metrics"

    t = AnalysisTarget(name, None)
    t._build_fn = build
    return t


def _ff_target(name: str, seeds=SEEDS, chunk=CHUNK) -> AnalysisTarget:
    base_name = name[:-len(FF_SUFFIX)]

    def build():
        import jax
        import jax.numpy as jnp

        from ..core.network import fast_forward_chunk, fast_forward_ok

        proto = _registry()[base_name]()
        assert fast_forward_ok(proto), base_name
        base = fast_forward_chunk(proto, chunk, seed_axis=True)

        def fn(net, pstate):
            net, pstate, _ = base(net, pstate)
            return net, pstate

        args = jax.vmap(proto.init)(jnp.arange(seeds, dtype=jnp.int32))
        return fn, args, proto, "fast_forward"

    t = AnalysisTarget(name, None)
    t._build_fn = build
    return t


@functools.lru_cache(maxsize=1)
def target_names() -> tuple:
    return tuple(sorted(_registry()) +
                 sorted(f"{n}{FF_SUFFIX}" for n in FF_PROTOCOLS) +
                 sorted(f"{n}{METRICS_SUFFIX}" for n in METRICS_PROTOCOLS) +
                 sorted(f"{n}{FFM_SUFFIX}" for n in FFM_PROTOCOLS) +
                 sorted(f"{n}{TRACE_SUFFIX}" for n in TRACE_PROTOCOLS) +
                 sorted(f"{n}{AUDIT_SUFFIX}" for n in AUDIT_PROTOCOLS) +
                 sorted(f"{n}{CHAOS_SUFFIX}" for n in CHAOS_PROTOCOLS) +
                 sorted(f"{n}{MATRIX_SUFFIX}" for n in MATRIX_PROTOCOLS) +
                 sorted(f"{n}{TENANCY_SUFFIX}"
                        for n in TENANCY_PROTOCOLS) +
                 sorted(f"{n}{MEMO_SUFFIX}" for n in MEMO_PROTOCOLS) +
                 sorted(f"{n}{JOURNAL_SUFFIX}"
                        for n in JOURNAL_PROTOCOLS) +
                 sorted(SS_PROTOCOLS) + sorted(ROUTE_PROTOCOLS) +
                 [PHASE_TARGET])


def get_target(name: str) -> AnalysisTarget:
    reg = _registry()
    if name == PHASE_TARGET:
        return _phase_target(name)
    if name in SS_PROTOCOLS:
        return _ss_target(name)
    if name in ROUTE_PROTOCOLS:
        return _route_target(name)
    if name.endswith(ROUTE_SUFFIX):
        raise KeyError(f"unknown pallas-route target {name!r}; known: "
                       f"{sorted(ROUTE_PROTOCOLS)}")
    if name.endswith(MATRIX_SUFFIX):
        if name[:-len(MATRIX_SUFFIX)] not in MATRIX_PROTOCOLS:
            raise KeyError(
                f"unknown matrix target {name!r}; known: "
                f"{sorted(f'{n}{MATRIX_SUFFIX}' for n in MATRIX_PROTOCOLS)}")
        return _matrix_target(name)
    if name.endswith(TENANCY_SUFFIX):
        if name[:-len(TENANCY_SUFFIX)] not in TENANCY_PROTOCOLS:
            raise KeyError(
                f"unknown tenancy target {name!r}; known: "
                f"{sorted(f'{n}{TENANCY_SUFFIX}' for n in TENANCY_PROTOCOLS)}")
        return _tenancy_target(name)
    if name.endswith(MEMO_SUFFIX):
        if name[:-len(MEMO_SUFFIX)] not in MEMO_PROTOCOLS:
            raise KeyError(
                f"unknown memo target {name!r}; known: "
                f"{sorted(f'{n}{MEMO_SUFFIX}' for n in MEMO_PROTOCOLS)}")
        return _memo_target(name)
    if name.endswith(JOURNAL_SUFFIX):
        if name[:-len(JOURNAL_SUFFIX)] not in JOURNAL_PROTOCOLS:
            raise KeyError(
                f"unknown journal target {name!r}; known: "
                f"{sorted(f'{n}{JOURNAL_SUFFIX}' for n in JOURNAL_PROTOCOLS)}")
        return _journal_target(name)
    if name.endswith(CHAOS_SUFFIX):
        if name[:-len(CHAOS_SUFFIX)] not in CHAOS_PROTOCOLS:
            raise KeyError(
                f"unknown chaos target {name!r}; known: "
                f"{sorted(f'{n}{CHAOS_SUFFIX}' for n in CHAOS_PROTOCOLS)}")
        return _chaos_target(name)
    if name.endswith(AUDIT_SUFFIX):
        if name[:-len(AUDIT_SUFFIX)] not in AUDIT_PROTOCOLS:
            raise KeyError(
                f"unknown audit target {name!r}; known: "
                f"{sorted(f'{n}{AUDIT_SUFFIX}' for n in AUDIT_PROTOCOLS)}")
        return _audit_target(name)
    if name.endswith(TRACE_SUFFIX):
        if name[:-len(TRACE_SUFFIX)] not in TRACE_PROTOCOLS:
            raise KeyError(
                f"unknown trace target {name!r}; known: "
                f"{sorted(f'{n}{TRACE_SUFFIX}' for n in TRACE_PROTOCOLS)}")
        return _trace_target(name)
    if name.endswith(FFM_SUFFIX):
        if name[:-len(FFM_SUFFIX)] not in FFM_PROTOCOLS:
            raise KeyError(
                f"unknown ff-metrics target {name!r}; known: "
                f"{sorted(f'{n}{FFM_SUFFIX}' for n in FFM_PROTOCOLS)}")
        return _ffm_target(name)
    if name.endswith(METRICS_SUFFIX):
        if name[:-len(METRICS_SUFFIX)] not in METRICS_PROTOCOLS:
            raise KeyError(
                f"unknown metrics target {name!r}; known: "
                f"{sorted(f'{n}{METRICS_SUFFIX}' for n in METRICS_PROTOCOLS)}")
        return _metrics_target(name)
    if name.endswith(FF_SUFFIX):
        if name[:-len(FF_SUFFIX)] not in FF_PROTOCOLS:
            raise KeyError(f"unknown fast-forward target {name!r}; "
                           f"known: {sorted(f'{n}{FF_SUFFIX}' for n in FF_PROTOCOLS)}")
        return _ff_target(name)
    if name not in reg:
        raise KeyError(f"unknown analysis target {name!r}; "
                       f"known: {sorted(target_names())}")
    return AnalysisTarget.from_protocol(name, reg[name])
