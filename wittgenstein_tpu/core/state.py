"""Core state pytrees: struct-of-arrays node state + time-bucketed mailbox.

Reference mapping (SURVEY.md §7.1):
  - Node objects in ``allNodes`` (reference core/Node.java:22-107) become one
    pytree of ``[N]``-shaped arrays in HBM (`NodeState`).
  - The per-ms linked-list buckets (``MsgsSlot``/``MessageStorage``, reference
    core/Network.java:108-299) become a fixed-shape ring of inbox slots
    ``[H, N, C]`` (`NetState.box_*`): H = horizon in ms, C = per-(node, ms)
    delivery capacity.  Slot fill counts make validity implicit (a slot c is
    live iff ``c < box_count[h, n]``), so there is no mask array to maintain.
  - Multicast envelopes with recomputed latencies (reference
    core/Envelope.java:45-155) become the broadcast table ``bc_*``: a
    broadcast is O(1) state (src, sent-time, payload, seed); every
    destination's arrival time is recomputed in-kernel each ms from the
    counter-based PRNG.  This is what makes ``sendAll`` to 10^6 nodes free.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
from flax import struct

# World map used by the reference for node positions (core/Node.java:15-18):
# 2000 x 1112 Mercator-projected map, distances on a torus in x and y.
MAX_X = 2000
MAX_Y = 1112
MAX_DIST = int((((MAX_X / 2.0) ** 2) + ((MAX_Y / 2.0) ** 2)) ** 0.5)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine shape parameters (hashable; safe to close over in jit).

    horizon must exceed the largest deliverable latency + 2: with
    ``spill_cap == 0`` arrivals are clamped to ``t + horizon - 1`` and
    counted in `NetState.clamped` (the reference instead supports arbitrary
    future arrivals via its rolling 60 s slot list, Network.java:201-299;
    `msg_discard_time` (Network.java:36-40) already legitimises dropping
    very-late messages).  With ``spill_cap > 0`` a far-future side buffer
    restores the reference's unbounded-horizon semantics for UNICASTS:
    arrivals past the ring are parked in `NetState.sp_*` and re-injected
    into the ring when it advances within reach — hour-scale timers
    (sendArriveAt, Network.java:384-390) no longer force a huge ring, only
    a spill slot per concurrently-parked message.  Broadcasts always clamp
    (their per-dest arrivals are recomputed inside the ring window); size
    `horizon` for the broadcast latency tail.
    """

    n: int
    horizon: int = 512
    inbox_cap: int = 8          # C: max unicast deliveries per (node, ms)
    payload_words: int = 2      # F: int32 payload words per message
    out_deg: int = 1            # K: max unicast sends per node per ms
    bcast_slots: int = 4        # B: max concurrently in-flight broadcasts
    msg_discard_time: int = 1 << 30
    spill_cap: int = 0          # S: far-future parked messages (0 = clamp)
    # P: ring planes are split into P node-range sub-planes of N/P nodes
    # each, so each sub-plane's flat int32 index H*Ns*C stays below 2^31
    # at 1M-class node counts, while the flat per-sub-plane layout stays
    # identical (bit-equal for any P —
    # tests/test_engine.py::test_box_split_bit_equal).
    box_split: int = 1

    @property
    def inbox_width(self):
        return self.inbox_cap + self.bcast_slots

    @property
    def split_n(self):
        """Nodes per ring sub-plane."""
        return self.n // self.box_split


@struct.dataclass
class NodeState:
    """All per-node engine state, ``[N]``-shaped (reference core/Node.java)."""

    x: jnp.ndarray              # int32 [N], 1..MAX_X  (Node.java:30-36)
    y: jnp.ndarray              # int32 [N], 1..MAX_Y
    city: jnp.ndarray           # int32 [N], -1 = no city (Node.java cityName)
    speed_ratio: jnp.ndarray    # float32 [N]  (Node.java:60)
    extra_latency: jnp.ndarray  # int32 [N]    (Node.java:43, Tor model)
    down: jnp.ndarray           # bool [N]     (Node.java:69, stop()/start())
    byzantine: jnp.ndarray      # bool [N]     (Node.java:50)
    done_at: jnp.ndarray        # int32 [N], 0 = not done (Node.java:72)
    partition: jnp.ndarray      # int32 [N]    (Network.java:639-649)
    msg_sent: jnp.ndarray       # int32 [N]    counters (Node.java:75-79)
    msg_received: jnp.ndarray
    bytes_sent: jnp.ndarray
    bytes_received: jnp.ndarray

    @property
    def n(self):
        return self.x.shape[-1]

    @property
    def alive(self):
        return ~self.down


def default_nodes(n: int) -> NodeState:
    # One fresh buffer per field: donation ("donate_argnums") forbids the same
    # buffer appearing twice in an executable's arguments.
    def zi():
        return jnp.zeros((n,), jnp.int32)

    return NodeState(
        x=jnp.ones((n,), jnp.int32),
        y=jnp.ones((n,), jnp.int32),
        city=jnp.full((n,), -1, jnp.int32),
        speed_ratio=jnp.ones((n,), jnp.float32),
        extra_latency=zi(),
        down=jnp.zeros((n,), bool),
        byzantine=jnp.zeros((n,), bool),
        done_at=zi(),
        partition=zi(),
        msg_sent=zi(),
        msg_received=zi(),
        bytes_sent=zi(),
        bytes_received=zi(),
    )


@struct.dataclass
class NetState:
    """Full simulator state: advance with `engine.step_ms`; pure + jittable."""

    time: jnp.ndarray           # int32 scalar, milliseconds (Network.java:45-49)
    seed: jnp.ndarray           # int32 scalar — base seed; all draws derive from it
    nodes: NodeState
    # Unicast mailbox ring, logically [H, N, C] but stored FLAT (1-D) so the
    # scan-carry layout and the scatter/slice layouts agree — multi-dim ring
    # buffers made XLA:TPU relayout the whole ring every iteration (hundreds
    # of MB/step).  The F payload words live in F separate PLANES (not one
    # [F*H*N*C] buffer): per-plane scatters need no cross-field OOB
    # sentinel arithmetic, and each plane's flat index range is F times
    # smaller.  Each plane is further split into
    # P = cfg.box_split node-range SUB-planes of Ns = N/P nodes (int32
    # index range at 100k-1M node counts): cell (h, n, c) with
    # n in sub-range j lives at flat index (h*Ns + n - j*Ns)*C + c of
    # sub-plane j.  P == 1 reproduces the round-3 layout exactly.
    box_data: tuple             # F*P x int32 [H*Ns*C] (plane f*P + j)
    box_src: tuple              # P x int32 [H*Ns*C]
    box_size: tuple             # P x int32 [H*Ns*C]
    box_count: jnp.ndarray      # int32 [H, N] — slots filled per (ms, node)
    # Broadcast table [B] (sendAll with recomputed per-dest latencies):
    bc_active: jnp.ndarray      # bool [B]
    bc_src: jnp.ndarray         # int32 [B]
    bc_time: jnp.ndarray        # int32 [B] — network time at send
    bc_payload: jnp.ndarray     # int32 [B, F]
    bc_size: jnp.ndarray        # int32 [B]
    bc_seed: jnp.ndarray        # int32 [B] — per-broadcast latency seed
    # Far-future spill buffer [S] (see EngineConfig.spill_cap); arrival < 0
    # marks a free slot:
    sp_arrival: jnp.ndarray     # int32 [S] — absolute arrival time
    sp_src: jnp.ndarray         # int32 [S]
    sp_dest: jnp.ndarray        # int32 [S]
    sp_size: jnp.ndarray        # int32 [S]
    sp_payload: jnp.ndarray     # int32 [S, F]
    dropped: jnp.ndarray        # int32 scalar — overflowed unicast deliveries
    bc_dropped: jnp.ndarray     # int32 scalar — broadcasts lost to a full table
    clamped: jnp.ndarray        # int32 scalar — arrivals clamped to the ring edge
    sp_dropped: jnp.ndarray     # int32 scalar — far-future sends lost to a full spill


def init_net(cfg: EngineConfig, nodes: NodeState, seed) -> NetState:
    h, n, c, f, b = (cfg.horizon, cfg.n, cfg.inbox_cap, cfg.payload_words,
                     cfg.bcast_slots)
    p = cfg.box_split
    if n % p:
        raise ValueError(f"box_split {p} must divide node count {n}")
    ns = cfg.split_n
    if h * ns * c >= 1 << 31:
        # Flat ring indices are int32, per sub-plane; beyond this raise
        # box_split or shard the node axis across devices.
        raise ValueError(
            f"mailbox ring sub-plane too large for int32 flat indexing: "
            f"{h}x{ns}x{c} >= 2^31; shrink horizon/inbox_cap or raise "
            f"box_split / shard the node axis across devices")
    return NetState(
        time=jnp.asarray(0, jnp.int32),
        # + 0 forces a fresh buffer: protocols keep their own copy of the
        # seed in pstate, and under donation the same buffer must not
        # appear twice in an executable's arguments.
        seed=jnp.asarray(seed, jnp.int32) + 0,
        nodes=nodes,
        box_data=tuple(jnp.zeros((h * ns * c,), jnp.int32)
                       for _ in range(f * p)),
        box_src=tuple(jnp.zeros((h * ns * c,), jnp.int32)
                      for _ in range(p)),
        box_size=tuple(jnp.zeros((h * ns * c,), jnp.int32)
                       for _ in range(p)),
        box_count=jnp.zeros((h, n), jnp.int32),
        bc_active=jnp.zeros((b,), bool),
        bc_src=jnp.zeros((b,), jnp.int32),
        bc_time=jnp.zeros((b,), jnp.int32),
        bc_payload=jnp.zeros((b, f), jnp.int32),
        bc_size=jnp.zeros((b,), jnp.int32),
        bc_seed=jnp.zeros((b,), jnp.int32),
        sp_arrival=jnp.full((cfg.spill_cap,), -1, jnp.int32),
        sp_src=jnp.zeros((cfg.spill_cap,), jnp.int32),
        sp_dest=jnp.zeros((cfg.spill_cap,), jnp.int32),
        sp_size=jnp.zeros((cfg.spill_cap,), jnp.int32),
        sp_payload=jnp.zeros((cfg.spill_cap, f), jnp.int32),
        dropped=jnp.asarray(0, jnp.int32),
        bc_dropped=jnp.asarray(0, jnp.int32),
        clamped=jnp.asarray(0, jnp.int32),
        sp_dropped=jnp.asarray(0, jnp.int32),
    )


@struct.dataclass
class Inbox:
    """What a node sees at time t: up to C unicast + B broadcast deliveries.

    The per-delivery ``action`` callback of the reference
    (core/messages/Message.java:action, dispatched at Network.java:625)
    becomes: the protocol step reads this whole batch at once.
    """

    data: jnp.ndarray   # int32 [N, S, F]   S = C + B
    src: jnp.ndarray    # int32 [N, S]
    valid: jnp.ndarray  # bool [N, S]


@struct.dataclass
class Outbox:
    """What every node wants to send after processing time t.

    Unicast: up to K messages per node (dest < 0 = unused slot).
    Broadcast: at most one `sendAll` request per node per ms — matches the
    reference where sendAll is a single envelope regardless of fan-out
    (Envelope.java:57-155).
    """

    dest: jnp.ndarray           # int32 [N, K]
    payload: jnp.ndarray        # int32 [N, K, F]
    size: jnp.ndarray           # int32 [N, K]
    delay: jnp.ndarray          # int32 [N, K] — extra ms before the latency
    bcast: jnp.ndarray          # bool [N]
    bcast_payload: jnp.ndarray  # int32 [N, F]
    bcast_size: jnp.ndarray     # int32 [N]
    # Static slot-id offset for NARROW outboxes (K < cfg.out_deg): the
    # engine keys each message's latency draw on the stable id
    # `src * cfg.out_deg + slot0 + column`, so a step that can only use a
    # contiguous sub-range of its outbox slots (e.g. a phase-hinted ms
    # where just the fast-path slots are live) may return only those
    # columns and still draw bit-identical latencies.
    slot0: int = struct.field(pytree_node=False, default=0)


def empty_outbox(cfg: EngineConfig, k: int | None = None,
                 slot0: int = 0) -> Outbox:
    n, f = cfg.n, cfg.payload_words
    k = cfg.out_deg if k is None else k
    return Outbox(
        dest=jnp.full((n, k), -1, jnp.int32),
        payload=jnp.zeros((n, k, f), jnp.int32),
        size=jnp.ones((n, k), jnp.int32),
        delay=jnp.zeros((n, k), jnp.int32),
        bcast=jnp.zeros((n,), bool),
        bcast_payload=jnp.zeros((n, f), jnp.int32),
        bcast_size=jnp.ones((n,), jnp.int32),
        slot0=slot0,
    )
