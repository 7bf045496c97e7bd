"""`stream`: the request plane's chunk program, driven back to back.

The program is the one `serve.registry.CompileRegistry.chunk_fn` builds
for the cell's `ScenarioSpec` (plane None: the plain engine the request
plane launches), so the benchmark times what users get.  Parameters of
a mix (`traffic/<mix>.json`):

  seeds        seeds per batch (the vmapped/seed-folded width)
  chunk_ms     simulated ms per launch
  span_ms      a batch's trajectory; reaching it re-inits the next batch
  trace_chunks chunks in the traced window of a ``--trace 1`` run
  check        {"seeds": seeds of the batch compared (drawn from
                `--seed`), "chunks": boundaries the reference follows
                from the start}

Set-up: find the protocol's reference (`check.reference`: refused here,
before anything is built, where no module or two claim it), build the
protocol, jit the seed init (`jax.vmap(proto.init)`, one program), warm
init, chunk and the check's reduction with one chunk on a throwaway
batch.  Window, from t = 0: init the batch of seeds drawn from
`--seed`, launch chunks back to back with one chunk queued ahead, read
a scalar of each as it completes, stop launching at `--seconds`.

A ``--trace 1`` run traces `trace_chunks` chunks, reads the device's
memory peak, then reduces the trace to busy time and idle gaps
(`trace.py`) and to device time per engine stage (`stages.py`, with the
chunk program's op names from a compile that loads the same executable
from the cache: after the peak is read, so it stays out of it), and
deletes the trace.  The set-up's trace and compile seconds are the
registry's `compile_stats` at the window's start.
"""

from __future__ import annotations

import shutil
import time

import numpy as np

from .. import check, stages, trace as trace_mod
from ..harness import device_block

#: seeds of the throwaway warm-up batch (never a window's seeds: those
#: are drawn from `--seed`)
WARM_SEED0 = 2 ** 30


def spec_of(config: dict, mix: dict, attack=None):
    """The cell's resolved `ScenarioSpec`: the configuration's spec with
    the mix's batch width, chunk and span (and the control's planted
    `attack`, if any)."""
    from wittgenstein_tpu.serve.spec import ScenarioSpec
    body = dict(config["spec"])
    body.update(seeds=list(range(mix["seeds"])), chunk_ms=mix["chunk_ms"],
                sim_ms=mix["span_ms"], attack=attack)
    return ScenarioSpec.from_json(body).validate()


def batch_seeds(seed: int, batch: int, width: int) -> np.ndarray:
    """`width` distinct int32 seeds for batch `batch` of run `seed`."""
    rng = np.random.default_rng([seed % 2 ** 64, batch])
    while True:
        s = rng.integers(0, 2 ** 31 - 1, size=width, dtype=np.int64)
        if len(np.unique(s)) == width:
            return s.astype(np.int32)


def reference_params(spec) -> dict:
    params = dict(spec.params)
    if spec.latency_model is not None:
        params["network_latency_name"] = spec.latency_model
    return params


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp

    import wittgenstein_tpu.models  # noqa: F401  (registers protocols)
    from wittgenstein_tpu.serve.registry import CompileRegistry

    ann = jax.profiler.TraceAnnotation
    mix = ctx["traffic"]
    width, chunk_ms = mix["seeds"], mix["chunk_ms"]
    spec = spec_of(ctx["config"], mix, ctx.get("attack"))
    ref = check.reference(ctx["root"], spec.protocol)
    rng = np.random.default_rng([ctx["seed"] % 2 ** 64, 1 << 20])
    sample = check.sample_seeds(rng, width, mix["check"]["seeds"])
    sample_dev = jnp.asarray(sample, jnp.int32)

    # ---------------------------------------------------------- set-up
    # The protocol builds its tables lazily, when the programs are first
    # traced, so one span covers the build, the compile (or the compile
    # cache's load) and one warm chunk.
    t = time.monotonic()
    registry = CompileRegistry()
    with ann("bench.build"):
        proto = spec.build_protocol()
    program = chunk = registry.chunk_fn(spec, None, proto=proto)
    if ctx["patch"] is not None:
        chunk = ctx["patch"](chunk)
    init = jax.jit(lambda s: jax.vmap(proto.init)(s))
    summarize = jax.jit(check.summarizer(ref))
    with ann("bench.load"):
        warm = init(jnp.arange(width, dtype=jnp.int32) + WARM_SEED0)
        warm = chunk(*warm)
        jax.block_until_ready(summarize(*warm, sample_dev))
    load_s = time.monotonic() - t
    del warm

    # ---------------------------------------------------------- window
    traced = ctx["trace"]
    tdir = ctx["out_dir"] / "trace"
    if traced:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
    compiles = registry.compile_stats()
    t_w0 = time.monotonic()
    setup_s = ctx["pre_start"] + (t_w0 - ctx["t0"])
    with ann("bench.window"):
        seeds, done = _window(ctx, mix, init, chunk, summarize, sample_dev,
                              t_w0)
    if traced:
        jax.profiler.stop_trace()
    device = device_block(ctx["devices"])

    # ---------------------------------------------------------- record
    wall = done[-1]["t"]
    record = {
        "setup": {"setup_s": setup_s, "load_s": load_s,
                  "compile": compiles},
        "window": {"seconds": wall, "chunks": len(done),
                   "seed_sim_ms": width * chunk_ms * len(done),
                   "lockstep_ms": chunk_ms * len(done)},
        "device": device, "attempted": width * len(done)}
    if traced:
        shapes = jax.eval_shape(init, jax.ShapeDtypeStruct((width,),
                                                           jnp.int32))
        record.update(reduce_trace(tdir, record, program, shapes))
    t = time.monotonic()
    record.update(_compare(ref, spec, mix, seeds[0], sample, done))
    record["check_s"] = time.monotonic() - t
    return record


def reduce_trace(tdir, record, program=None, shapes=None) -> dict:
    """A traced window's busy time, idle gaps and (given the chunk
    `program` and its state `shapes`) device time per stage, for the
    record; the trace is deleted.  Sets ``device.busy_s`` and
    ``device.window_s``.  Without an accelerator plane (the CPU) there
    is nothing to read: the trace reads None and no stage is given."""
    red = trace_mod.reduce_dir(tdir)
    out = {"trace": red}
    if red is not None:
        record["device"]["busy_s"] = red["busy_s"]
        record["device"]["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
        if program is not None:
            sim = record["window"]["seed_sim_ms"]
            st = stages.reduce_dir(
                tdir, stages.chunk_op_names(program, shapes), sim)
            red["stages"] = st["ms_per_simms"]
            red["sub"] = {k: 1000.0 * v / sim for k, v in st["sub"].items()}
            out["breakdown"]["device_stages"] = stages.device_stages(st)
    shutil.rmtree(tdir, ignore_errors=True)
    return out


def _window(ctx, mix, init, chunk, summarize, sample_dev, t_w0):
    """Init the first batch, then chunks back to back, one queued ahead
    of the one being waited for; returns each batch's seeds and every
    completed chunk (its time, batch, boundary ms and summary)."""
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation as ann
    width, chunk_ms = mix["seeds"], mix["chunk_ms"]
    batch, at_ms = 0, 0
    seeds = {0: batch_seeds(ctx["seed"], 0, width)}
    with ann("bench.init"):
        state = init(jnp.asarray(seeds[0]))
    queued, done, launched = [], [], 0
    while True:
        with ann("bench.launch"):
            state = chunk(*state)
            queued.append((batch, at_ms + chunk_ms,
                           summarize(*state, sample_dev)))
        launched += 1
        at_ms += chunk_ms
        if len(queued) > 1:
            with ann("bench.sync"):
                _complete(queued.pop(0), done, t_w0)
        if (launched >= mix["trace_chunks"] if ctx["trace"] else
                time.monotonic() - t_w0 >= ctx["seconds"]):
            break
        if at_ms >= mix["span_ms"]:
            batch, at_ms = batch + 1, 0
            seeds[batch] = batch_seeds(ctx["seed"], batch, width)
            with ann("bench.init"):
                state = init(jnp.asarray(seeds[batch]))
    with ann("bench.sync"):
        while queued:
            _complete(queued.pop(0), done, t_w0)
    return seeds, done


def _complete(entry, done, t_w0):
    import jax
    batch, ms, summ = entry
    summ = jax.device_get(summ)          # waits for the chunk
    done.append({"t": time.monotonic() - t_w0, "batch": batch, "ms": ms,
                 "summ": summ})


def _compare(ref, spec, mix, seeds0, sample, done) -> dict:
    """`mismatched_values` (see `check`) beside its limit, and the
    seeds found wrong."""
    first = [d for d in done if d["batch"] == 0]
    ends = {d["batch"]: d for d in done}.values()    # each batch's last
    wrong, bad = 0, set()
    for d in done:                      # every seed's clock
        off = np.flatnonzero(np.asarray(d["summ"]["time"]) != d["ms"])
        wrong += len(off)
        bad.update((d["batch"], int(i)) for i in off)
    for d in ends:                      # every seed's losses
        lost = np.flatnonzero(np.asarray(d["summ"]["lost"]))
        wrong += len(lost)
        bad.update((d["batch"], int(i)) for i in lost)
    n_ref = min(len(first), mix["check"]["chunks"])
    params = reference_params(spec)
    refs = check.reference_trajectories(
        ref, params, [int(seeds0[i]) for i in sample], mix["chunk_ms"],
        n_ref)
    for j, (i, want) in enumerate(zip(sample, refs)):  # value for value
        prog = [{**{f: d["summ"][f][j] for f in check.fields(ref)},
                 "time": d["summ"]["time"][i]} for d in first[:n_ref]]
        n = check.mismatches(ref, prog, want)
        wrong += n
        if n:
            bad.add((0, int(i)))
    return {"checks": {"mismatched_values": {
                "value": wrong, "limit": check.LIMITS["mismatched_values"]}},
            "failed": len(bad)}
