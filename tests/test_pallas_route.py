"""Bit-equality of the fused Pallas routing megakernel
(ops/pallas_route.py, interpret mode on CPU) against the XLA
sort/scatter binning of `core/network._bin_into_ring` — the full
trajectory pytrees across engine variants, plus the routing edge
cases the sort path handles implicitly (full-ring overflow drop
ordering, spill park/unpark, same-ms tie-break stability, the
src == dst 1-ms floor), each parametrized over WTPU_PALLAS_ROUTE so
BOTH paths stay pinned.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wittgenstein_tpu.core import builders
from wittgenstein_tpu.core.batched import scan_chunk_batched
from wittgenstein_tpu.core.latency import (NetworkFixedLatency,
                                           NetworkNoLatency)
from wittgenstein_tpu.core.network import (Runner, _bin_into_ring,
                                           fast_forward_chunk, scan_chunk)
from wittgenstein_tpu.core.state import (EngineConfig, empty_outbox,
                                         init_net)
from wittgenstein_tpu.models.handel import Handel
from wittgenstein_tpu.models.pingpong import PingPong
from wittgenstein_tpu.ops.pallas_route import (forced, route_enabled,
                                               route_fixed_bytes,
                                               route_row_bytes, with_route)

ROUTE = "WTPU_PALLAS_ROUTE"

#: the two routing paths every edge-case test pins
BOTH = pytest.mark.parametrize("kernel", ["xla", "pallas"])


def _trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _ab(build, args):
    """Run one chunk build under both kernels; assert bit-identity and
    return the pallas result."""
    with forced("xla"):
        ox = jax.jit(build())(*args)
    with forced("pallas"):
        op = jax.jit(build())(*args)
    _trees_equal(ox, op)
    return op


def _floor_handel(**kw):
    params = dict(node_count=64, threshold=56, nodes_down=6,
                  pairing_time=4, dissemination_period_ms=20,
                  level_wait_time=50, fast_path=10, horizon=64,
                  network_latency_name="NetworkFixedLatency(16)")
    params.update(kw)
    return Handel(**params)


# ------------------------------------------------------- direct kernel


def test_route_enabled_resolution(monkeypatch):
    monkeypatch.delenv(ROUTE, raising=False)
    assert not route_enabled()
    monkeypatch.setenv(ROUTE, "1")
    assert route_enabled()
    # the serve plane's per-spec override beats the process env
    with forced("xla"):
        assert not route_enabled()
    monkeypatch.delenv(ROUTE, raising=False)
    with forced("pallas"):
        assert route_enabled()
    assert not route_enabled()          # context restored
    with pytest.raises(ValueError, match="pallas.*xla|xla.*pallas"):
        with forced("mosaic"):
            pass


def test_direct_bin_equality_randomized():
    """The strongest pin: randomized message batches straight through
    `_bin_into_ring` — heavy same-cell collisions (overflow + rank
    ties), invalid entries interleaved, multiple in-kernel waves
    (m > ROUTE_CHUNK), and a box_split=2 plane layout."""
    rng = np.random.default_rng(7)
    for split, m in ((1, 40), (1, 600), (2, 600)):
        cfg = EngineConfig(n=16, horizon=32, inbox_cap=3,
                           payload_words=2, out_deg=4, bcast_slots=0,
                           box_split=split)
        nodes = builders.NodeBuilder().build(0, cfg.n)
        net = init_net(cfg, nodes, 0)
        t = jnp.asarray(96, jnp.int32)      # mid-run, wrapped ring
        src = jnp.asarray(rng.integers(0, cfg.n, m), jnp.int32)
        # few distinct cells -> deep (rel, dest) groups + overflow
        dest = jnp.asarray(rng.integers(0, 5, m), jnp.int32)
        rel = jnp.asarray(rng.integers(1, cfg.horizon - 1, m), jnp.int32)
        payload = jnp.asarray(
            rng.integers(0, 1 << 20, (m, cfg.payload_words)), jnp.int32)
        size = jnp.asarray(rng.integers(1, 99, m), jnp.int32)
        valid = jnp.asarray(rng.random(m) < 0.8)
        with forced("xla"):
            net_x, drop_x = _bin_into_ring(cfg, net, t, src, dest,
                                           t + rel, payload, size, valid)
        with forced("pallas"):
            net_p, drop_p = _bin_into_ring(cfg, net, t, src, dest,
                                           t + rel, payload, size, valid)
        _trees_equal(net_x, net_p)
        assert int(drop_x) == int(drop_p)
        if m >= 600:
            assert int(drop_x) > 0          # the case really overflows


# -------------------------------------------------- engine bit-identity


def test_pingpong_dense_bit_identity():
    """Per-ms engine + broadcasts: every `_bin_into_ring` call (route +
    spill drain) of a 24-ms PingPong run is bit-identical."""
    proto = PingPong(node_count=64)
    args = proto.init(jnp.asarray(0, jnp.int32))
    _ab(lambda: scan_chunk(proto, 24), args)


def test_handel_batched_superstep_bit_identity():
    """The headline engine shape: seed-folded batched twin, fused K=4
    windows — ONE kernel launch bins the window's 4 concatenated
    outboxes across the whole seed batch."""
    proto = _floor_handel()
    args = jax.vmap(proto.init)(jnp.arange(2, dtype=jnp.int32))
    _ab(lambda: scan_chunk_batched(proto, 16, superstep=4), args)


@pytest.mark.slow
def test_handel_vmapped_superstep_bit_identity():
    proto = _floor_handel()
    args = jax.vmap(proto.init)(jnp.arange(2, dtype=jnp.int32))
    _ab(lambda: jax.vmap(scan_chunk(proto, 16, superstep=4)), args)


@pytest.mark.slow
def test_handel_fast_forward_bit_identity():
    proto = _floor_handel()
    args = jax.vmap(proto.init)(jnp.arange(2, dtype=jnp.int32))

    def build():
        base = fast_forward_chunk(proto, 16, seed_axis=True, superstep=2)

        def run(n_, p_):
            n2, p2, _ = base(n_, p_)
            return n2, p2

        return run

    _ab(build, args)


@pytest.mark.slow
def test_box_split_bit_identity():
    proto = _floor_handel()
    proto.cfg = dataclasses.replace(proto.cfg, box_split=2)
    args = proto.init(jnp.asarray(1, jnp.int32))
    _ab(lambda: scan_chunk(proto, 16, superstep=2), args)


@pytest.mark.slow
def test_sharded_local_ring_bit_identity():
    """ShardedRunner's local-ring binning through the kernel on the
    virtual CPU mesh."""
    from jax.sharding import Mesh

    from wittgenstein_tpu.parallel.sharded import RingForward, \
        ShardedRunner
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    proto = RingForward(n=64, stride=9, latency=10)
    mesh = Mesh(np.array(devs[:8]), ("sp",))

    def sh_run():
        sr = ShardedRunner(proto, mesh, xcap=32)
        snet, sps = sr.init(0)
        snet, sps = sr.run_ms(snet, sps, 40)
        return sr.gather_nodes(snet), sps

    with forced("xla"):
        a = sh_run()
    with forced("pallas"):
        b = sh_run()
    _trees_equal(a, b)


# ------------------------------------------------------------ obs planes


def test_metrics_plane_identical_with_kernel_on():
    """The obs taps read the SAME state either way: the instrumented
    trajectory AND the interval counters agree across kernels."""
    from wittgenstein_tpu.obs import MetricsSpec
    from wittgenstein_tpu.obs.engine import scan_chunk_metrics
    proto = PingPong(node_count=64)
    spec = MetricsSpec(stat_each_ms=4)
    args = proto.init(jnp.asarray(0, jnp.int32))
    _ab(lambda: scan_chunk_metrics(proto, 24, spec), args)


def test_ring_conservation_audit_clean_with_kernel_on(monkeypatch):
    """THE acceptance pin: the compiled conservation-law monitors see
    a clean ring with the megakernel ON, and the audited trajectory is
    bit-identical to the XLA path's."""
    from wittgenstein_tpu.obs.audit import AuditSpec

    def audited(kernel):
        with forced(kernel):
            r = Runner(PingPong(node_count=64), donate=False,
                       audit=AuditSpec())
            net, ps = r.protocol.init(jnp.asarray(0, jnp.int32))
            net, ps = r.run_ms(net, ps, 40)
            return (net, ps), r.audit_stats()
    state_x, stats_x = audited("xla")
    state_p, stats_p = audited("pallas")
    _trees_equal(state_x, state_p)
    assert stats_p["clean"], stats_p
    assert "ring_conservation" in stats_p["invariants"]
    assert stats_x == stats_p


# ----------------------------------------------------- routing edge cases


class Storm:
    """Every node unicasts node 0 at t == 0 with NoLatency: one
    (ms, dest) cell takes the whole batch — the overflow/tie-break
    microscope.  Node 0 records the src column of its delivery row."""

    def __init__(self, n=8, cap=4):
        self.latency = NetworkNoLatency()
        self.cfg = EngineConfig(n=n, horizon=64, inbox_cap=cap,
                                payload_words=2, out_deg=1,
                                bcast_slots=2)

    def init(self, seed):
        nodes = builders.NodeBuilder().build(seed, self.cfg.n)
        return init_net(self.cfg, nodes, seed), {
            "srcs": jnp.full(self.cfg.inbox_cap, -1, jnp.int32),
            "got": jnp.zeros(self.cfg.n, jnp.int32)}

    def step(self, pstate, nodes, inbox, t, key):
        out = empty_outbox(self.cfg)
        out = out.replace(
            dest=jnp.where(t == 0, 0, -1) *
            jnp.ones((self.cfg.n, 1), jnp.int32),
            payload=jnp.broadcast_to(
                jnp.arange(self.cfg.n, dtype=jnp.int32)[:, None, None],
                (self.cfg.n, 1, self.cfg.payload_words)))
        got = jnp.sum(inbox.valid, 1).astype(jnp.int32)
        uc = inbox.src[0, :self.cfg.inbox_cap]
        seen = jnp.any(inbox.valid[0])
        return {"srcs": jnp.where(
                    seen & (pstate["srcs"][0] < 0),
                    jnp.where(inbox.valid[0, :self.cfg.inbox_cap], uc, -1),
                    pstate["srcs"]),
                "got": pstate["got"] + got}, nodes, out


@BOTH
def test_full_ring_overflow_drop_ordering(kernel):
    """cap 4, 8 same-cell sends: EXACTLY the 4 lowest-slot senders (the
    stable concatenation order) land, in slot order 0..3; the 4
    overflow entries are counted — identically on both kernels."""
    proto = Storm(n=8, cap=4)
    with forced(kernel):
        net, p = proto.init(0)
        net, p = Runner(proto, donate=False).run_ms(net, p, 6)
    assert int(net.dropped) == 4
    assert int(p["got"][0]) == 4
    assert list(np.asarray(p["srcs"])) == [0, 1, 2, 3]


@BOTH
def test_same_ms_tiebreak_stability(kernel):
    """Same-(ms, dest) rank is INPUT order (the stable sort's tie
    rule): with capacity for everyone, slots hold src 0..n-1 in
    order."""
    proto = Storm(n=6, cap=8)
    with forced(kernel):
        net, p = proto.init(0)
        net, p = Runner(proto, donate=False).run_ms(net, p, 6)
    assert int(net.dropped) == 0
    assert list(np.asarray(p["srcs"]))[:6] == [0, 1, 2, 3, 4, 5]


class OneShot:
    """test_engine's OneShot, local copy: node 0 -> `dest` at t=0."""

    def __init__(self, latency, dest=1, cfg=None, delay=0,
                 all_send=False):
        self.latency = latency
        self.cfg = cfg or EngineConfig(n=4, horizon=64, inbox_cap=4,
                                       payload_words=2, out_deg=1,
                                       bcast_slots=2)
        self.dest, self.delay, self.all_send = dest, delay, all_send

    def init(self, seed):
        nodes = builders.NodeBuilder().build(seed, self.cfg.n)
        return init_net(self.cfg, nodes, seed), {
            "got": jnp.zeros(self.cfg.n, jnp.int32),
            "when": jnp.full(self.cfg.n, -1, jnp.int32)}

    def step(self, pstate, nodes, inbox, t, key):
        out = empty_outbox(self.cfg)
        ids = jnp.arange(self.cfg.n)
        sender = jnp.ones_like(ids, bool) if self.all_send else (ids == 0)
        dest = ((ids + 1) % self.cfg.n if self.all_send
                else jnp.full_like(ids, self.dest))
        out = out.replace(
            dest=jnp.where(sender & (t == 0), dest, -1)[:, None],
            size=jnp.full((self.cfg.n, 1), 7, jnp.int32),
            delay=jnp.full((self.cfg.n, 1), self.delay, jnp.int32))
        got = jnp.sum(inbox.valid, 1).astype(jnp.int32)
        return {"got": pstate["got"] + got,
                "when": jnp.where((got > 0) & (pstate["when"] < 0), t,
                                  pstate["when"])}, nodes, out


@BOTH
def test_spill_park_unpark_exact_delivery(kernel):
    """Far-future send parks in the spill buffer, unparks when the
    ring reaches it, and delivers EXACTLY on time — the drain's
    binning goes through the selected kernel too."""
    cfg = EngineConfig(n=4, horizon=64, inbox_cap=4, payload_words=2,
                       out_deg=1, bcast_slots=2, spill_cap=8)
    proto = OneShot(NetworkFixedLatency(10), cfg=cfg, delay=500)
    with forced(kernel):
        net, p = proto.init(0)
        net, p = Runner(proto, donate=False).run_ms(net, p, 520)
    assert int(p["when"][1]) == 511     # send t=0 + 1 + delay 500 + lat 10
    assert int(jnp.sum(p["got"])) == 1
    assert int(net.clamped) == 0 and int(net.sp_dropped) == 0
    assert int(net.dropped) == 0
    assert int(jnp.sum(net.sp_arrival >= 0)) == 0      # slot freed


@BOTH
def test_spill_overflow_drop_ordering(kernel):
    """4 far sends into 2 spill slots: the 2 lowest-index senders park
    (deterministic free-slot order), 2 are counted dropped — and the
    parked ones still deliver, identically on both kernels."""
    cfg = EngineConfig(n=4, horizon=64, inbox_cap=4, payload_words=2,
                       out_deg=1, bcast_slots=2, spill_cap=2)
    proto = OneShot(NetworkFixedLatency(10), cfg=cfg, delay=500,
                    all_send=True)
    with forced(kernel):
        net, p = proto.init(0)
        net, p = Runner(proto, donate=False).run_ms(net, p, 520)
    assert int(net.sp_dropped) == 2
    assert int(jnp.sum(p["got"])) == 2
    # survivors are the first two senders' targets (stable park order)
    assert list(np.asarray(p["got"])) == [0, 1, 1, 0]


@BOTH
def test_self_send_one_ms_floor(kernel):
    """src == dst pins latency to 1 ms regardless of the model
    (full_latency) — arrival t+2 on both kernels."""
    proto = OneShot(NetworkFixedLatency(50), dest=0)
    with forced(kernel):
        net, p = proto.init(0)
        net, p = Runner(proto, donate=False).run_ms(net, p, 10)
    assert int(p["when"][0]) == 2


# ------------------------------------------------------------ cost model


def test_route_block_obeys_tiling_or_refuses():
    """A real launch's destination block obeys the TPU (8, 128) tiling
    rule (the [1, H, blk] count block: blk a multiple of 128 or every
    destination) inside the VMEM model, or the launcher refuses at
    build time naming the rule and the XLA path — the headline ring
    (H256 x C12, F=2: ~1 MB per destination) is refused.  Interpret
    mode still picks a block (CPU tests never see Mosaic)."""
    from wittgenstein_tpu.ops.pallas_route import _pick_route_block
    with pytest.raises(ValueError, match=r"\(8, 128\) tiling.*XLA"):
        _pick_route_block(2048, 4096, 256, 12, 2, 256)
    for ns in (128, 256, 64):
        blk = _pick_route_block(ns, 256, 8, 4, 1, 256)
        assert blk % 128 == 0 or blk == ns
        assert ns % blk == 0
        assert route_row_bytes(8, 4, 1) * blk + \
            route_fixed_bytes(256, 1) <= 6 << 20
    huge = dict(ns=64, m=256, horizon=1 << 15, cap=512, f=8, chunk=256)
    with pytest.raises(ValueError, match="VMEM"):
        _pick_route_block(**huge, enforce=True)
    assert _pick_route_block(**huge, enforce=False) == 1


def test_with_route_wraps_tracing():
    calls = []

    def fn(x):
        calls.append(route_enabled())
        return x

    with_route(fn, "pallas")(1)
    with_route(fn, "xla")(1)
    assert calls == [True, False]
