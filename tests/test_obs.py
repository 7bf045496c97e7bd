"""The on-device metrics plane (wittgenstein_tpu/obs).

Two invariants, per the package contract:

  * metrics-ON is simulation-bit-identical: the full (NetState, pstate)
    pytree after an instrumented chunk equals the uninstrumented
    engine's, for the dense scan (PingPong, Handel exact + cardinal,
    Dfinity), the batched seed-folded engine, and the fast-forward
    while loops (whose skip stats must also match);
  * the recorded series is EXACT accounting, not sampling noise: per-
    interval deltas of every cumulative counter sum to the final-state
    counter deltas, executed-ms counts + skipped-ms cover the chunk,
    and quiet intervals forward-fill to a flat line.

Protocol configs mirror tests/test_fast_forward.py so the reference
compiles share its persistent-cache entries.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wittgenstein_tpu.core.batched import scan_chunk_batched
from wittgenstein_tpu.core.network import Runner, scan_chunk
from wittgenstein_tpu.obs import (MetricsFrame, MetricsSpec,
                                  counter_values, engine_metrics_block,
                                  fast_forward_chunk_metrics,
                                  scan_chunk_batched_metrics,
                                  scan_chunk_metrics, to_perfetto,
                                  to_progress_csv)


def _trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _protocols():
    from wittgenstein_tpu.models.dfinity import Dfinity
    from wittgenstein_tpu.models.handel import Handel
    from wittgenstein_tpu.models.pingpong import PingPong

    return {
        "Handel": lambda: Handel(
            node_count=64, threshold=56, nodes_down=6, pairing_time=4,
            dissemination_period_ms=20, level_wait_time=50, fast_path=10),
        "HandelCardinal": lambda: Handel(
            node_count=64, threshold=56, nodes_down=6, pairing_time=4,
            dissemination_period_ms=20, fast_path=10, mode="cardinal"),
        "Dfinity": lambda: Dfinity(block_producers_count=10,
                                   attesters_count=10,
                                   attesters_per_round=10),
        "PingPong": lambda: PingPong(node_count=64),
    }


def _check_frame_accounting(frame, net, executed_ms):
    """The recorded series is exact: cumulative-counter deltas sum to
    the final state, samples count every executed ms."""
    t = frame.totals()
    nodes = net.nodes
    assert t["samples"] == executed_ms
    assert t["msg_sent"] == int(np.asarray(nodes.msg_sent).sum())
    assert t["msg_received"] == int(np.asarray(nodes.msg_received).sum())
    assert t["bytes_sent"] == int(np.asarray(nodes.bytes_sent).sum())
    assert t["bytes_received"] == int(
        np.asarray(nodes.bytes_received).sum())
    assert t["drop_count"] == int(
        np.asarray(net.dropped).sum() + np.asarray(net.bc_dropped).sum() +
        np.asarray(net.clamped).sum() + np.asarray(net.sp_dropped).sum())
    # interval-delta sums telescope to the same totals
    for name in ("msg_sent", "bytes_received", "drop_count"):
        assert int(frame.deltas(name).sum()) == t[name], name


@pytest.mark.parametrize("name", ["PingPong", "Handel", "HandelCardinal",
                                  "Dfinity"])
def test_metrics_on_bit_identical_and_exact(name):
    proto = _protocols()[name]()
    ms, seeds = 320, 2
    spec = MetricsSpec(stat_each_ms=20)
    sd = jnp.arange(seeds, dtype=jnp.int32)

    nets, ps = jax.vmap(proto.init)(sd)
    ref = jax.jit(jax.vmap(scan_chunk(proto, ms)))(nets, ps)
    nets, ps = jax.vmap(proto.init)(sd)
    net2, ps2, mc = jax.jit(jax.vmap(scan_chunk_metrics(proto, ms, spec)))(
        nets, ps)

    _trees_equal(ref, (net2, ps2))
    frame = MetricsFrame.from_carry(spec, mc)
    assert frame.n_intervals == spec.n_intervals(ms)
    _check_frame_accounting(frame, net2, seeds * ms)


def test_metrics_on_bit_identical_batched_engine():
    proto = _protocols()["Handel"]()
    ms, seeds = 160, 2
    spec = MetricsSpec(stat_each_ms=20)
    sd = jnp.arange(seeds, dtype=jnp.int32)
    nets, ps = jax.vmap(proto.init)(sd)
    ref = jax.jit(scan_chunk_batched(proto, ms))(nets, ps)
    nets, ps = jax.vmap(proto.init)(sd)
    net2, ps2, mc = jax.jit(scan_chunk_batched_metrics(proto, ms, spec))(
        nets, ps)
    _trees_equal(ref, (net2, ps2))
    frame = MetricsFrame.from_carry(spec, mc)
    _check_frame_accounting(frame, net2, seeds * ms)


def test_metrics_fast_forward_bit_identical_and_covers_chunk():
    from wittgenstein_tpu.core.network import fast_forward_chunk

    proto = _protocols()["PingPong"]()
    ms, seeds = 320, 2
    spec = MetricsSpec(stat_each_ms=20)
    sd = jnp.arange(seeds, dtype=jnp.int32)
    nets, ps = jax.vmap(proto.init)(sd)
    ref = jax.jit(fast_forward_chunk(proto, ms, seed_axis=True))(nets, ps)
    nets, ps = jax.vmap(proto.init)(sd)
    net2, ps2, stats, mc = jax.jit(
        fast_forward_chunk_metrics(proto, ms, spec, seed_axis=True))(
        nets, ps)
    _trees_equal(ref[:2], (net2, ps2))
    skipped = int(np.asarray(stats["skipped_ms"]))
    assert skipped == int(np.asarray(ref[2]["skipped_ms"]))
    assert skipped > 0          # PingPong is quiet-window heavy

    frame = MetricsFrame.from_carry(spec, mc)
    t = frame.totals()
    # per-seed lockstep recorders: the batch sum is seeds x the shared
    # skip accounting, and samples + skips tile the whole chunk exactly
    assert t["ff_skipped_ms"] == seeds * skipped
    assert t["samples"] + t["ff_skipped_ms"] == seeds * ms
    assert t["ff_jumps"] == seeds * int(np.asarray(stats["jump_count"]))
    _check_frame_accounting(frame, net2, seeds * (ms - skipped))
    # quiet intervals hold samples == 0 and forward-fill flat
    samples = frame.column("samples")
    filled = frame.filled("msg_sent")
    raw = frame.column("msg_sent")
    assert (samples == 0).any()
    for i in range(1, frame.n_intervals):
        if samples[i] == 0:
            assert filled[i] == filled[i - 1]
        else:
            assert filled[i] == raw[i]


def test_counter_values_reads_engine_state_exactly():
    proto = _protocols()["PingPong"]()
    net, _ = proto.init(0)
    spec = MetricsSpec()
    net = net.replace(
        box_count=net.box_count.at[3, 5].set(2).at[7, 1].set(1),
        bc_active=net.bc_active.at[0].set(True),
        dropped=jnp.asarray(4, jnp.int32),
        clamped=jnp.asarray(1, jnp.int32))
    vals = {k: int(v) for k, v in counter_values(spec, net).items()}
    assert vals["ring_rows"] == 2
    assert vals["ring_occupancy"] == 3
    assert vals["bc_live"] == 1
    assert vals["drop_count"] == 5
    assert vals["live_count"] == proto.cfg.n
    assert vals["done_count"] == 0
    assert vals["spill_hwm"] == 0       # spill_cap == 0: nothing parked


def test_metrics_spec_validation_and_layout():
    with pytest.raises(ValueError, match="stat_each_ms"):
        MetricsSpec(stat_each_ms=0)
    with pytest.raises(ValueError, match="unknown counters"):
        MetricsSpec(counters=("msg_sent", "nope"))
    # canonical ordering regardless of the order passed
    spec = MetricsSpec(counters=("drop_count", "samples", "msg_sent"))
    assert spec.columns == ("samples", "msg_sent", "drop_count")
    assert spec.col("drop_count") == 2 and spec.col("ff_jumps") is None
    assert spec.n_intervals(95) == 10
    # a disabled-ff spec records steps fine (record_jump is a no-op)
    proto = _protocols()["PingPong"]()
    net, ps = proto.init(0)
    out = jax.jit(scan_chunk_metrics(proto, 40, spec))(net, ps)
    assert out[2].series.shape == (4, 3)


def test_exporters_csv_perfetto_bench_block():
    proto = _protocols()["PingPong"]()
    spec = MetricsSpec(stat_each_ms=20)
    ms = 200
    net, ps = proto.init(0)
    net2, ps2, mc = jax.jit(scan_chunk_metrics(proto, ms, spec))(net, ps)
    frame = MetricsFrame.from_carry(spec, mc)

    csv = str(to_progress_csv(frame))
    lines = csv.strip().splitlines()
    assert lines[0].startswith("time,samples,msg_sent,msg_sent_cum")
    assert len(lines) == 1 + frame.n_intervals
    # cumulative column of the last row equals the final counter
    header = lines[0].split(",")
    last = dict(zip(header, lines[-1].split(",")))
    assert int(last["msg_sent_cum"]) == int(
        np.asarray(net2.nodes.msg_sent).sum())

    trace = to_perfetto(frame)
    evs = trace["traceEvents"]
    # Chrome-trace conventions: metadata process_name + "X"/"C"
    # events with ts/dur in us
    assert any(e.get("ph") == "M" and e.get("name") == "process_name"
               for e in evs)
    xs = [e for e in evs if e.get("ph") == "X"]
    cs = [e for e in evs if e.get("ph") == "C"]
    assert len(xs) == frame.n_intervals        # dense run: all executed
    assert xs[0]["dur"] == spec.stat_each_ms * 1000
    assert cs and all("value" in e["args"] for e in cs)

    blk = engine_metrics_block(frame)
    assert blk["intervals"] == frame.n_intervals
    assert blk["totals"]["msg_sent"] == int(
        np.asarray(net2.nodes.msg_sent).sum())
    assert blk["series"]["time"][-1] == ms
    import json
    json.dumps(blk)                            # one-line-JSON embeddable

    # long series are summarized, never silently truncated
    big = MetricsFrame(spec=spec, t0=0,
                       series=np.zeros((100, len(spec.columns)), np.int64))
    assert engine_metrics_block(big).get("series_truncated") is True


def test_runner_fast_forward_and_metrics():
    from wittgenstein_tpu.utils.profiling import run_report

    proto = _protocols()["PingPong"]()
    spec = MetricsSpec(stat_each_ms=20)
    r0 = Runner(proto)
    net, ps = proto.init(0)
    ref = r0.run_ms(net, ps, 200)

    r1 = Runner(proto, fast_forward=True, metrics=spec)
    net, ps = proto.init(0)
    out = r1.run_ms(net, ps, 100)
    out = r1.run_ms(*out, 100)                  # chunked: carries stitch
    _trees_equal(ref, out)
    st = r1.ff_stats()
    assert st["skipped_ms"] > 0
    frame = r1.metrics_frame()
    assert frame.n_intervals == 10
    assert frame.totals()["ff_skipped_ms"] == st["skipped_ms"]
    assert frame.totals()["samples"] + st["skipped_ms"] == 200

    rep = run_report(out[0], wall_s=0.25, ff=st)
    assert f"skipped={st['skipped_ms']}ms" in rep
    assert "skip_rate=" in rep
    # without ff stats the report omits the fields rather than faking 0
    assert "skipped" not in run_report(out[0])


def test_sharded_runner_metrics_twin():
    from jax.sharding import Mesh
    from wittgenstein_tpu.parallel.sharded import RingForward, ShardedRunner

    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    proto = RingForward(n=64, stride=9, latency=10)
    runner = ShardedRunner(proto, mesh)
    spec = MetricsSpec(stat_each_ms=4)
    snet, ps = runner.init(3)
    snet, ps, mc = runner.run_ms(snet, ps, 24, metrics=spec)
    frame = MetricsFrame.from_carry(spec, mc)
    t = frame.totals()
    nodes = runner.gather_nodes(snet)
    assert t["samples"] == 24
    assert t["msg_sent"] == int(nodes.msg_sent.sum())
    assert t["msg_received"] == int(nodes.msg_received.sum())
    assert t["live_count"] == 64
    # and the metrics run didn't perturb the simulation: same state as
    # the uninstrumented sharded run
    snet2, ps2 = runner.init(3)
    snet2, ps2 = runner.run_ms(snet2, ps2, 24)
    _trees_equal((snet, ps), (snet2, ps2))


def test_harness_on_device_progress_series():
    # the ProgressPerTime analogue with sampling moved on device: same
    # program shape as the ff-metrics test above (one compile, cached)
    from wittgenstein_tpu.core.harness import progress_per_time_on_device

    proto = _protocols()["PingPong"]()
    frame, nets, ps = progress_per_time_on_device(
        proto, run_count=2, max_time=320, stat_each_ms=20,
        fast_forward=True)
    t = frame.totals()
    assert t["samples"] + t["ff_skipped_ms"] == 2 * 320
    assert t["msg_sent"] == int(np.asarray(nets.nodes.msg_sent).sum())
    assert frame.n_intervals == 16


def test_zero_cost_rule_catches_dead_instrumentation():
    from wittgenstein_tpu.analysis.rules_metrics import MetricsZeroCostRule
    from wittgenstein_tpu.analysis.targets import AnalysisTarget

    def plain_chunk(x, y):
        def body(c, _):
            return (c[0] + 1, c[1] * 2), ()
        c, _ = jax.lax.scan(body, (x, y), length=3)
        return c

    rule = MetricsZeroCostRule()
    args = (jnp.zeros((4,), jnp.int32), jnp.ones((4,), jnp.float32))
    clean = AnalysisTarget.from_fn("fake", plain_chunk, args)
    fs = rule.run(clean, {})
    vals = {f.metric: f.value for f in fs if f.metric}
    assert vals["carry_extra_leaves"] == 0
    assert not [f for f in fs if f.severity == "error"]

    # the same uninstrumented build labeled as a metrics target = a
    # silently-dead plane, which must be an error
    dead = AnalysisTarget.from_fn("fake+metrics", plain_chunk, args)
    errs = [f for f in rule.run(dead, {}) if f.severity == "error"]
    assert errs and "silently dead" in errs[0].message
