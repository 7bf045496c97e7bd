"""What decides `correct`: the timed path's trajectory against the plain
reference (`reference/`), value for value.

The simulator is deterministic, so the comparison is exact and its one
number, `mismatched_values`, has the limit 0.  At every chunk boundary
of the window the program's state is reduced on the device to, for the
seeds the mix checks (`check.seeds`: every seed of the batch in both
cells), each node's results (done time, message and byte counters: the
reference's Node.java counters), the protocol's per-node verification
counters, the clock and the loss counters (dropped, clamped, evicted).
The reference (`reference/`, numpy on the host, written apart from the
program) runs each of those seeds from its start, one millisecond at a
time, to the same boundaries.  Every seed of the batch is held besides
to its clock at each boundary and to no message lost.  Each value that
differs counts one.
"""

from __future__ import annotations

import numpy as np

#: per-node results compared: engine counters (NodeState) ...
NODE_FIELDS = ("done_at", "msg_sent", "msg_received", "bytes_sent",
               "bytes_received")
#: ... and the protocol's own per-node counters
PROTO_FIELDS = ("sigs_checked", "msg_filtered")
#: loss counters (per seed): none is lost in a sound run
LOSS_FIELDS = ("dropped", "clamped", "evicted")


def summarize(nets, ps, sample):
    """Batched state ([S, ...] leaves) -> the compared values.  Jitted
    by the caller; `sample` is an int32 index array into the batch."""
    out = {"time": nets.time,
           "lost": nets.dropped + nets.clamped + ps.evicted}
    for f in NODE_FIELDS:
        out[f] = getattr(nets.nodes, f)[sample]
    for f in PROTO_FIELDS:
        out[f] = getattr(ps, f)[sample]
    out.update(dropped=nets.dropped[sample], clamped=nets.clamped[sample],
               evicted=ps.evicted[sample])
    return out


def sample_seeds(rng: np.random.Generator, batch: int, k: int):
    """Indices of `k` seeds of the batch, drawn from the run's seed."""
    return np.sort(rng.choice(batch, size=min(k, batch), replace=False))


def mismatches(program: list, reference: list) -> int:
    """Values that differ, over all boundaries, fields and nodes.
    `program[b][f]` is the field at boundary b for ONE sampled seed."""
    n = 0
    for p, r in zip(program, reference):
        for f in NODE_FIELDS + PROTO_FIELDS + LOSS_FIELDS + ("time",):
            a, b = np.asarray(p[f]), np.asarray(r[f])
            n += int(a.size) if a.shape != b.shape else int(
                np.count_nonzero(a != b))
    return n


def _trajectory(job):
    from .reference import sim
    return sim.trajectory(*job)


def device_tables():
    """The reference's float32 latency tables, evaluated by XLA on the
    device the cell ran on (see `reference.world.latency_tables`)."""
    import functools

    import jax
    import jax.numpy as jnp

    from .reference import world

    def evaluate(fn, *arrays):
        return np.asarray(jax.jit(functools.partial(fn, jnp))(*arrays))
    return world.latency_tables(evaluate)


def reference_trajectories(params: dict, seeds, chunk_ms: int,
                           n_chunks: int) -> list:
    """The plain reference's compared values, per seed, at each of the
    first `n_chunks` chunk boundaries: numpy on the host, one process
    per seed (at most `WORKERS` at a time), none of which touches JAX."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor
    bounds = [chunk_ms * (k + 1) for k in range(n_chunks)]
    tables = device_tables()
    jobs = [(params, int(s), bounds, tables) for s in seeds]
    if len(jobs) == 1:
        return [_trajectory(jobs[0])]
    with ProcessPoolExecutor(min(len(jobs), WORKERS),
                             mp_context=multiprocessing.get_context(
                                 "spawn")) as pool:
        return list(pool.map(_trajectory, jobs))


#: reference processes at a time (the one-chip host has 13 cores)
WORKERS = 12


#: each compared number's limit, set in PERF.md from the readings (an
#: exact comparison has the limit 0)
LIMITS = {"mismatched_values": 0}
