"""The request plane's phase-specialized chunk program.

`CompileRegistry` builds the batched engine's plain-plane program in
the compact phase-specialized form (`core/network.phase_plan`) when the
protocol declares a static schedule and the chunk is a multiple of its
block.  That is sound only because every way the request plane enters
a chunk program enters at a multiple of `chunk_ms`, hence at phase 0:
these tests pin the build decision, its bit-identity, that entry-time
contract, and that the compact form keeps the plain program's memory.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import wittgenstein_tpu.models  # noqa: F401 — fill the registry
from wittgenstein_tpu.core.batched import scan_chunk_batched
from wittgenstein_tpu.serve import (CompileRegistry, ForkState,
                                    ScenarioSpec, Scheduler)

HANDEL = {"node_count": 64, "nodes_down": 6, "threshold": 57,
          "pairing_time": 4, "level_wait_time": 50,
          "dissemination_period_ms": 20, "fast_path": 10,
          "horizon": 64, "inbox_cap": 12}


def _trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _spec(**kw):
    base = dict(protocol="Handel", params=HANDEL, seeds=(0, 1),
                sim_ms=60, chunk_ms=20, engine="batched", superstep=2,
                obs=())
    base.update(kw)
    return ScenarioSpec(**base).validate()


def test_registry_specializes_handel_bit_identically():
    spec = _spec()
    proto = spec.build_protocol()
    reg = CompileRegistry(persistent=False)
    fn = reg.chunk_fn(spec, None, proto=proto)
    assert reg.registry_block()["phase_specialized"] == 1
    assert "phase_specialized" not in reg.stats()
    plain = jax.jit(scan_chunk_batched(proto, spec.chunk_ms))
    a = b = jax.vmap(proto.init)(jnp.asarray(spec.seeds, jnp.int32))
    # three chunks: past the first level wait (50 ms)
    for _ in range(3):
        a, b = fn(*a), plain(*b)
        _trees_equal(a, b)
    assert int(a[0].time[0]) == spec.sim_ms > HANDEL["level_wait_time"]
    assert int(np.asarray(a[1].sigs_checked).sum()) > 0


@pytest.mark.parametrize("case", ["chunk10", "pingpong", "fast_forward",
                                  "metrics"])
def test_registry_builds_plain(case):
    """A chunk that is no multiple of the schedule block, a protocol
    with no static schedule, the fast-forward engine and the obs
    planes keep today's unspecialized program."""
    plane = None
    if case == "chunk10":
        spec = _spec(chunk_ms=10)
    elif case == "pingpong":
        spec = ScenarioSpec(protocol="PingPong",
                            params={"node_count": 64}, seeds=(0,),
                            sim_ms=40, chunk_ms=20, obs=()).validate()
    elif case == "fast_forward":
        spec = _spec(engine="fast_forward")
    else:
        spec, plane = _spec(obs=("metrics",), stat_each_ms=20), "metrics"
    reg = CompileRegistry(persistent=False)
    reg.chunk_fn(spec, plane)
    assert reg.registry_block()["phase_specialized"] == 0


def test_request_plane_enters_at_chunk_multiples(tmp_path):
    """Every way the request plane enters a chunk program — a fresh
    lane, a lane resumed from a checkpoint, a lane forked from a memo
    prefix, a lane back from preemption — enters at a multiple of
    `chunk_ms`, so the specialized program always starts at phase 0."""
    reg = CompileRegistry(persistent=False)
    entries = []

    def record(fn, nets, *rest):
        entries.append(np.asarray(nets.time).tolist())
        return fn(nets, *rest)

    spec = _spec(seeds=(0,))
    chunk = spec.chunk_ms

    def run(sched, rid):
        sched.run_pending()
        req = sched.request(rid)
        assert req.status == "done", req.error
        return req

    # fresh lane
    first = Scheduler(registry=reg, launcher=record)
    fresh = run(first, first.submit(spec))
    assert reg.phase_specialized == 1

    # a lane resumed from a checkpoint: the first scheduler dies after
    # one chunk, a fresh one resumes from the file
    ck = str(tmp_path / "ck")
    launched = []

    def killer(fn, *args):
        if launched:
            raise RuntimeError("KILLED")
        launched.append(1)
        return record(fn, *args)

    crashed = Scheduler(registry=reg, launcher=killer, max_retries=0,
                        retry_backoff_s=0.0, checkpoint_dir=ck)
    crashed.submit(spec)
    crashed.run_pending()
    resumer = Scheduler(registry=reg, launcher=record, checkpoint_dir=ck)
    (rid,) = resumer.resume_checkpoints()
    assert run(resumer, rid).resumed_from_ms == chunk

    # a lane forked from a prefix at a chunk boundary
    pre = Scheduler(registry=reg, launcher=record)
    prefix = run(pre, pre.submit(_spec(seeds=(0,), sim_ms=chunk)))
    forker = Scheduler(registry=reg, launcher=record)
    rid = forker.submit(spec, fork=ForkState(
        state=prefix.final_state, carries={}, at_ms=chunk,
        prefix_digest="prefix"))
    assert run(forker, rid).forked_from["fork_ms"] == chunk

    # a lane back from preemption by a higher-priority request
    sched = Scheduler(registry=reg, launcher=record)
    hi = {}

    def boundary():
        if not hi:
            hi["rid"] = sched.submit(ScenarioSpec(
                protocol="PingPong", params={"node_count": 32},
                seeds=(7,), sim_ms=20, chunk_ms=20, obs=(),
                priority=5))
    sched.on_boundary = boundary
    lo = sched.submit(spec)
    assert run(sched, lo).preempted >= 1

    assert all(t % chunk == 0 for ts in entries for t in ts)
    assert {t for ts in entries for t in ts} >= {0, chunk, 2 * chunk}
    _trees_equal(fresh.final_state, sched.request(lo).final_state)
    assert reg.phase_specialized == 1


def _temp_bytes(fn, args):
    return jax.jit(fn).lower(*args).compile().memory_analysis() \
        .temp_size_in_bytes


def test_phase_specialized_temp_bytes_match_plain():
    """The compact form's compiled temporaries stay within 5% above
    the plain program's at a small cardinal shape (CPU, 1,024 nodes x
    2 seeds: 30.5 MB plain, 29.0 MB compact); unrolling every superstep
    of the block, as the vmapped `scan_chunk` does, doubles them (60.0
    MB here; 573 MB against 1,134 MB at 4,096 nodes)."""
    proto = _spec(params={**HANDEL, "node_count": 1024, "nodes_down": 102,
                          "threshold": 912, "mode": "cardinal"},
                  ).build_protocol()
    args = jax.eval_shape(jax.vmap(proto.init),
                          jnp.arange(2, dtype=jnp.int32))
    plain = _temp_bytes(scan_chunk_batched(proto, 20), args)
    spec = _temp_bytes(scan_chunk_batched(proto, 20, t0_mod=0), args)
    assert plain > 0
    assert spec <= 1.05 * plain, (spec, plain)
