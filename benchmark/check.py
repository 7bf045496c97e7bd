"""What decides `correct`: the timed path's trajectory against the plain
reference of its protocol, value for value.

The reference is found by protocol: every module under the cell's
checkout's ``benchmark/reference/`` that declares ``PROTOCOL`` claims
that registry class name (`reference/__init__.py` lists what such a
module declares), and exactly one module may claim a cell's protocol.
So a configuration of another protocol brings its reference as a file,
and nothing here names a protocol.

The simulator is deterministic, so the comparison is exact and its one
number, `mismatched_values`, has the limit 0.  At every chunk boundary
of the window the program's state is reduced on the device to, for the
seeds the mix checks (`check.seeds`), each node's results (the module's
``NODE_FIELDS`` of the engine's node state), the protocol's per-node
counters (``PROTO_FIELDS``), the clock and the losses (the engine's
dropped and clamped messages and the protocol's ``PROTO_LOSSES``).  The
reference (numpy on the host, written apart from the program) runs each
of those seeds from its start, one millisecond at a time, to the same
boundaries.  Every seed of the batch is held besides to its clock at
each boundary and to no message lost.  Each value that differs counts
one.
"""

from __future__ import annotations

import functools
import hashlib
import importlib.util
import sys
import types
from pathlib import Path

import numpy as np

#: the engine's loss counters (per seed); a protocol adds its own
ENGINE_LOSSES = ("dropped", "clamped")


class NoReference(LookupError):
    """No reference module, or more than one, claims a protocol."""


# ------------------------------------------------------------- lookup

@functools.lru_cache(maxsize=None)
def _modules(ref_dir: str) -> tuple:
    """Every module of `ref_dir`, loaded by path as one package of its
    own (so a module's relative imports find its neighbours there, and
    two checkouts never share a module)."""
    pkg = "benchmark_reference_" + hashlib.sha1(
        ref_dir.encode()).hexdigest()[:12]
    package = types.ModuleType(pkg)
    package.__path__ = [ref_dir]
    sys.modules[pkg] = package
    out = []
    for path in sorted(Path(ref_dir).glob("*.py")):
        if path.name == "__init__.py":
            continue
        name = f"{pkg}.{path.stem}"
        if name not in sys.modules:        # a neighbour imported it
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[name] = mod
            spec.loader.exec_module(mod)
        out.append(sys.modules[name])
    return tuple(out)


def reference(root, protocol: str):
    """The module under `<root>/reference/` (`root`: a checkout's
    ``benchmark/``) whose ``PROTOCOL`` is `protocol`; `NoReference`
    where none or several claim it."""
    ref_dir = str((Path(root) / "reference").resolve())
    claims = [m for m in (_modules(ref_dir) if Path(ref_dir).is_dir()
                          else ())
              if getattr(m, "PROTOCOL", None) == protocol]
    if len(claims) != 1:
        raise NoReference(
            f"{len(claims)} modules under {ref_dir} claim protocol "
            f"{protocol!r} ({[Path(m.__file__).name for m in claims]}); "
            "exactly one must declare PROTOCOL = " + repr(protocol))
    return claims[0]


def fields(ref) -> tuple:
    """The per-seed values compared at each boundary."""
    return (ref.NODE_FIELDS + ref.PROTO_FIELDS + ENGINE_LOSSES
            + ref.PROTO_LOSSES)


# ------------------------------------------------------------- program

def summarizer(ref):
    """``summarize(nets, ps, sample)``: batched state ([S, ...] leaves)
    -> the compared values.  Jitted by the caller; `sample` is an int32
    index array into the batch."""
    def summarize(nets, ps, sample):
        lost = sum(getattr(nets, f) for f in ENGINE_LOSSES)
        out = {"time": nets.time,
               "lost": lost + sum(getattr(ps, f) for f in ref.PROTO_LOSSES)}
        for f in ref.NODE_FIELDS:
            out[f] = getattr(nets.nodes, f)[sample]
        for f in ENGINE_LOSSES:
            out[f] = getattr(nets, f)[sample]
        for f in ref.PROTO_FIELDS + ref.PROTO_LOSSES:
            out[f] = getattr(ps, f)[sample]
        return out
    return summarize


def sample_seeds(rng: np.random.Generator, batch: int, k: int):
    """Indices of `k` seeds of the batch, drawn from the run's seed."""
    return np.sort(rng.choice(batch, size=min(k, batch), replace=False))


def mismatches(ref, program: list, reference_: list) -> int:
    """Values that differ, over all boundaries, fields and nodes.
    `program[b][f]` is the field at boundary b for ONE sampled seed."""
    n = 0
    for p, r in zip(program, reference_):
        for f in fields(ref) + ("time",):
            a, b = np.asarray(p[f]), np.asarray(r[f])
            n += int(a.size) if a.shape != b.shape else int(
                np.count_nonzero(a != b))
    return n


# ------------------------------------------------------------- reference

def _trajectory(job):
    ref_dir, protocol, *args = job
    return reference(Path(ref_dir).parent, protocol).trajectory(*args)


def device_tables(ref):
    """What the reference needs evaluated by XLA on the device the cell
    ran on (its ``tables(evaluate)``: float formulas whose last bit is
    the device's, e.g. Handel's latency tables)."""
    import jax
    import jax.numpy as jnp

    def evaluate(fn, *arrays):
        return np.asarray(jax.jit(functools.partial(fn, jnp))(*arrays))
    return ref.tables(evaluate)


def reference_trajectories(ref, params: dict, seeds, chunk_ms: int,
                           n_chunks: int) -> list:
    """The plain reference's compared values, per seed, at each of the
    first `n_chunks` chunk boundaries: numpy on the host, one process
    per seed (at most `WORKERS` at a time), none of which touches JAX;
    each loads the reference by the same path."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor
    bounds = [chunk_ms * (k + 1) for k in range(n_chunks)]
    tables = device_tables(ref)
    ref_dir = str(Path(ref.__file__).resolve().parent)
    jobs = [(ref_dir, ref.PROTOCOL, params, int(s), bounds, tables)
            for s in seeds]
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
