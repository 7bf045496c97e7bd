"""Engine semantics tests — the analogue of core NetworkTest.java /
EnvelopeStorageTest.java: delivery, ordering, counters, partitions, drops."""

import jax
import jax.numpy as jnp
import pytest

from wittgenstein_tpu.core import builders
from wittgenstein_tpu.core.latency import (NetworkFixedLatency,
                                           NetworkNoLatency,
                                           NetworkUniformLatency)
from wittgenstein_tpu.core.network import Runner, step_ms
from wittgenstein_tpu.core.state import (EngineConfig, empty_outbox, init_net)


class OneShot:
    """Minimal protocol: node 0 sends one unicast to node 1 at t=0; every
    node records the messages it sees."""

    def __init__(self, n=4, latency=None, dest=1, size=7, cfg=None,
                 delay=0, all_send=False):
        self.latency = latency or NetworkFixedLatency(10)
        self.cfg = cfg or EngineConfig(n=n, horizon=64, inbox_cap=4,
                                       payload_words=2, out_deg=1,
                                       bcast_slots=2)
        self.dest = dest
        self.size = size
        self.delay = delay
        self.all_send = all_send      # every node i -> (i+1) % n at t=0

    def init(self, seed):
        nodes = builders.NodeBuilder().build(seed, self.cfg.n)
        net = init_net(self.cfg, nodes, seed)
        p = {"got": jnp.zeros(self.cfg.n, jnp.int32),
             "when": jnp.full(self.cfg.n, -1, jnp.int32)}
        return net, p

    def step(self, pstate, nodes, inbox, t, key):
        out = empty_outbox(self.cfg)
        ids = jnp.arange(self.cfg.n)
        sender = jnp.ones_like(ids, bool) if self.all_send else (ids == 0)
        dest = ((ids + 1) % self.cfg.n if self.all_send
                else jnp.full_like(ids, self.dest))
        out = out.replace(
            dest=jnp.where(sender & (t == 0), dest, -1)[:, None],
            payload=jnp.broadcast_to(
                jnp.where(sender[:, None, None], 42, 0),
                (self.cfg.n, 1, self.cfg.payload_words)).astype(jnp.int32),
            size=jnp.full((self.cfg.n, 1), self.size, jnp.int32),
            delay=jnp.full((self.cfg.n, 1), self.delay, jnp.int32))
        got = jnp.sum(inbox.valid, 1).astype(jnp.int32)
        pstate = {
            "got": pstate["got"] + got,
            "when": jnp.where((got > 0) & (pstate["when"] < 0), t,
                              pstate["when"]),
        }
        return pstate, nodes, out


def run(protocol, ms, seed=0):
    net, p = protocol.init(seed)
    return Runner(protocol, donate=False).run_ms(net, p, ms)


def test_unicast_delivery_time_and_counters():
    # Fixed latency 10: send at t=0 -> sentTime 1 -> arrival 11
    # (Network.java:420-487 semantics: arrival = sendTime + latency).
    proto = OneShot(latency=NetworkFixedLatency(10))
    net, p = run(proto, 20)
    assert int(p["when"][1]) == 11
    assert int(p["got"][1]) == 1
    assert int(jnp.sum(p["got"])) == 1
    assert int(net.nodes.msg_sent[0]) == 1
    assert int(net.nodes.bytes_sent[0]) == 7
    assert int(net.nodes.msg_received[1]) == 1
    assert int(net.nodes.bytes_received[1]) == 7
    assert int(net.dropped) == 0


def test_self_send_min_latency():
    # from == to gives latency 1 (NetworkLatency.java:27-29): arrival t+2.
    proto = OneShot(latency=NetworkFixedLatency(50), dest=0)
    _, p = run(proto, 10)
    assert int(p["when"][0]) == 2


def test_down_node_does_not_receive():
    proto = OneShot()
    net, p = proto.init(0)
    net = net.replace(nodes=net.nodes.replace(
        down=jnp.arange(proto.cfg.n) == 1))
    net, p = Runner(proto, donate=False).run_ms(net, p, 20)
    assert int(jnp.sum(p["got"])) == 0
    assert int(net.nodes.msg_received[1]) == 0
    # the sender still counts the attempt (Network.java:475-477)
    assert int(net.nodes.msg_sent[0]) == 1


def test_partition_blocks_delivery():
    proto = OneShot()
    net, p = proto.init(0)
    part = jnp.where(jnp.arange(proto.cfg.n) == 1, 1, 0).astype(jnp.int32)
    net = net.replace(nodes=net.nodes.replace(partition=part))
    net, p = Runner(proto, donate=False).run_ms(net, p, 20)
    assert int(jnp.sum(p["got"])) == 0


class Broadcaster(OneShot):
    def step(self, pstate, nodes, inbox, t, key):
        out = empty_outbox(self.cfg)
        sender = jnp.arange(self.cfg.n) == 0
        out = out.replace(bcast=sender & (t == 0),
                          bcast_size=jnp.full((self.cfg.n,), 3, jnp.int32))
        got = jnp.sum(inbox.valid, 1).astype(jnp.int32)
        pstate = {
            "got": pstate["got"] + got,
            "when": jnp.where((got > 0) & (pstate["when"] < 0), t,
                              pstate["when"]),
        }
        return pstate, nodes, out


def test_broadcast_reaches_everyone_once():
    proto = Broadcaster(n=8, latency=NetworkUniformLatency(30))
    net, p = run(proto, 40)
    assert [int(v) for v in p["got"]] == [1] * 8
    # sendAll counts n attempted sends (Network.java:341-347)
    assert int(net.nodes.msg_sent[0]) == 8
    assert int(net.nodes.bytes_sent[0]) == 24
    # every delivery within [2, 33] ms
    assert int(jnp.min(p["when"])) >= 2
    assert int(jnp.max(p["when"])) <= 33


def test_broadcast_latencies_are_stable_recomputation():
    # Same seed => identical arrival times (the Envelope.java:45-56
    # recomputed-latency contract); different seed => different ones.
    proto = Broadcaster(n=16, latency=NetworkUniformLatency(200))
    _, p1 = run(proto, 250, seed=5)
    _, p2 = run(proto, 250, seed=5)
    _, p3 = run(proto, 250, seed=9)
    assert jnp.array_equal(p1["when"], p2["when"])
    assert not jnp.array_equal(p1["when"], p3["when"])


def test_inbox_overflow_counts_drops():
    # All 8 nodes unicast node 0 with NoLatency (everything lands at t+2)
    # and inbox_cap 4 -> exactly 4 dropped, deterministically.
    class Storm(OneShot):
        def step(self, pstate, nodes, inbox, t, key):
            out = empty_outbox(self.cfg)
            out = out.replace(dest=jnp.where(t == 0, 0, -1) *
                              jnp.ones((self.cfg.n, 1), jnp.int32))
            got = jnp.sum(inbox.valid, 1).astype(jnp.int32)
            return {"got": pstate["got"] + got, "when": pstate["when"]}, \
                nodes, out

    proto = Storm(n=8, latency=NetworkNoLatency())
    net, p = run(proto, 5)
    assert int(p["got"][0]) == 4
    assert int(net.dropped) == 4


def test_far_future_clamps_without_spill():
    # delay 500 >> horizon 64, spill_cap 0: the arrival is clamped to the
    # ring edge and counted (the documented bounded-horizon contract).
    proto = OneShot(latency=NetworkFixedLatency(10), delay=500)
    net, p = run(proto, 80)
    assert int(net.clamped) == 1
    assert int(p["when"][1]) == 63          # t0 send -> 1 + (horizon-2)


def test_spill_delivers_far_future_arrivals_exactly():
    """With spill_cap > 0, an arrival far past the ring parks in the spill
    buffer and is delivered EXACTLY on time — the reference's
    unbounded-horizon semantics (MessageStorage, Network.java:201-299;
    sendArriveAt :384-390) without sizing the ring for it."""
    cfg = EngineConfig(n=4, horizon=64, inbox_cap=4, payload_words=2,
                       out_deg=1, bcast_slots=2, spill_cap=8)
    proto = OneShot(latency=NetworkFixedLatency(10), cfg=cfg, delay=500)
    net, p = run(proto, 520)
    assert int(p["when"][1]) == 511         # send t=1 + delay 500 + lat 10
    assert int(p["got"][1]) == 1 and int(jnp.sum(p["got"])) == 1
    assert int(net.clamped) == 0 and int(net.sp_dropped) == 0
    assert int(net.dropped) == 0
    assert int(jnp.sum(net.sp_arrival >= 0)) == 0   # slot freed after drain


def test_spill_overflow_counts():
    cfg = EngineConfig(n=4, horizon=64, inbox_cap=4, payload_words=2,
                       out_deg=1, bcast_slots=2, spill_cap=2)
    proto = OneShot(latency=NetworkFixedLatency(10), cfg=cfg, delay=500,
                    all_send=True)
    net, p = run(proto, 520)
    assert int(net.sp_dropped) == 2         # 4 far sends, 2 spill slots
    assert int(jnp.sum(p["got"])) == 2      # survivors still delivered
    assert int(jnp.sum(net.sp_arrival >= 0)) == 0


def test_mailbox_ring_wraps():
    # Horizon 64, run 200 ms with periodic resends crossing the wrap point.
    class Periodic(OneShot):
        def step(self, pstate, nodes, inbox, t, key):
            out = empty_outbox(self.cfg)
            sender = jnp.arange(self.cfg.n) == 0
            out = out.replace(dest=jnp.where(sender & (t % 50 == 0), 1,
                                             -1)[:, None])
            got = jnp.sum(inbox.valid, 1).astype(jnp.int32)
            return {"got": pstate["got"] + got, "when": pstate["when"]}, \
                nodes, out

    proto = Periodic(latency=NetworkFixedLatency(10))
    net, p = run(proto, 200)
    assert int(p["got"][1]) == 4  # sends at t=0,50,100,150


@pytest.mark.slow
def test_long_run_ring_integrity():
    """NetworkTest.java:425-435 analog (100M-ms run, scaled to 1M): a
    periodic sender over a horizon-64 ring that wraps ~15 625 times must
    deliver every message exactly once with exact counters and no
    residue."""

    class Tick:
        def __init__(self):
            self.latency = NetworkFixedLatency(5)
            self.cfg = EngineConfig(n=4, horizon=64, inbox_cap=4,
                                    payload_words=2, out_deg=1,
                                    bcast_slots=2)

        def init(self, seed):
            nodes = builders.NodeBuilder().build(seed, self.cfg.n)
            return (init_net(self.cfg, nodes, seed),
                    {"got": jnp.zeros(self.cfg.n, jnp.int32)})

        def step(self, pstate, nodes, inbox, t, key):
            out = empty_outbox(self.cfg)
            sender = (jnp.arange(self.cfg.n) == 0) & (t % 100 == 0)
            out = out.replace(
                dest=jnp.where(sender, 1, -1)[:, None])
            got = jnp.sum(inbox.valid, 1).astype(jnp.int32)
            return {"got": pstate["got"] + got}, nodes, out

    proto = Tick()
    net, p = proto.init(0)
    runner = Runner(proto, donate=False)
    for _ in range(100):
        net, p = runner.run_ms(net, p, 10_000)
    # sends at t = 0, 100, ..., 999900 all arrive at t+6 < 1M.
    assert int(net.time) == 1_000_000
    assert int(p["got"][1]) == 10_000
    assert int(jnp.sum(p["got"])) == 10_000
    assert int(net.nodes.msg_sent[0]) == 10_000
    assert int(net.nodes.msg_received[1]) == 10_000
    assert int(net.dropped) == 0 and int(net.clamped) == 0
    assert int(jnp.sum(net.box_count)) == 0       # no residue in the ring


def test_determinism_under_jit_copy():
    # The copy()+init() reproducibility contract (HandelTest.java:14-34):
    # re-initialising from the same seed reproduces runs exactly.
    proto = Broadcaster(n=32, latency=NetworkUniformLatency(100))
    n1, p1 = run(proto, 150, seed=3)
    n2, p2 = run(proto, 150, seed=3)
    assert jnp.array_equal(p1["when"], p2["when"])
    assert jnp.array_equal(n1.nodes.msg_received, n2.nodes.msg_received)


@pytest.mark.slow      # tier-1 budget (reports/TIER1_DURATIONS.md)
def test_runner_donation_bit_identical():
    """Runner(donate=True) — the whole state donated to each chunk —
    must be bit-identical to the undonated runner, including across the
    chunk_limit split (on the CPU donation is a no-op; on a v5e it was
    checked at the headline state in PR 21)."""
    from wittgenstein_tpu.models.handel import Handel
    import jax
    proto = Handel(node_count=128, nodes_down=12, threshold=114,
                   pairing_time=4, dissemination_period_ms=20)
    outs = []
    for donate in (False, True):
        r = Runner(proto, donate=donate, chunk_limit=300)
        net, ps = proto.init(7)
        net, ps = r.run_ms(net, ps, 700)   # 300 + 300 + 100 chunks
        outs.append((net, ps))
    (n1, p1), (n2, p2) = outs
    for a, b in zip(jax.tree.leaves((n1, p1)), jax.tree.leaves((n2, p2))):
        assert jnp.array_equal(a, b)


def test_box_split_bit_equal():
    """EngineConfig.box_split (node-range ring sub-planes — keeps each
    sub-plane's flat int32 index in range at 100k-1M nodes) must be a
    pure layout change: full-pytree bit-equality at any P, including the
    reassembled inbox slices and every scatter path."""
    import dataclasses
    from wittgenstein_tpu.models.handel import Handel
    outs = []
    for p in (1, 2, 4):
        proto = Handel(node_count=128, nodes_down=12, threshold=114,
                       pairing_time=4, dissemination_period_ms=20)
        proto.cfg = dataclasses.replace(proto.cfg, box_split=p)
        r = Runner(proto, donate=False)
        net, ps = proto.init(3)
        net, ps = r.run_ms(net, ps, 300)
        outs.append((net, ps))
    import numpy as np
    base_net, base_ps = outs[0]
    # Compare the LOGICAL ring (concatenated sub-planes) + all other state.
    def logical(net, ps, p):
        cfg_h, cfg_n, cfg_c = 512, 128, 16
        ns = cfg_n // p
        def cat(planes):
            return np.concatenate(
                [np.asarray(pl).reshape(cfg_h, ns, cfg_c) for pl in planes],
                axis=1)
        f = len(net.box_data) // p
        data = [cat(net.box_data[fi * p:(fi + 1) * p]) for fi in range(f)]
        rest = [x for x in jax.tree.leaves((net, ps))
                if not any(x is y for y in
                           (*net.box_data, *net.box_src, *net.box_size))]
        return data, cat(net.box_src), cat(net.box_size), rest
    d0, s0, z0, rest0 = logical(base_net, base_ps, 1)
    for (net, ps), p in zip(outs[1:], (2, 4)):
        d, s, z, rest = logical(net, ps, p)
        for a, b in zip(d0, d):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(s0, s)
        np.testing.assert_array_equal(z0, z)
        assert len(rest0) == len(rest)
        for a, b in zip(rest0, rest):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
