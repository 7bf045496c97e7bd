"""The TPU-native discrete-event engine.

This replaces the reference's single-threaded event loop (core/Network.java:
receiveUntil/nextMessage, :533-637) with a fixed-shape, jit-compiled
millisecond step driven by `lax.scan`:

  per ms t:   inbox  = mailbox slice [N, C] + broadcast recompute [N, B]
              state' = protocol.step(state, inbox, t)       # all N nodes at once
              mailbox.scatter(outbox arrivals)              # sort-based binning

Determinism comes for free: every random draw is a pure function of
(seed, t, ids) via `ops.prng`, and same-ms delivery order is fixed by the
stable sort in the scatter — the tensor analogue of the reference's
deterministic same-ms linked lists (Network.java:108-115).

Design notes vs the reference:
  * UNICAST arrivals beyond ``t + horizon - 1`` park in the spill buffer
    when ``cfg.spill_cap > 0`` (delivered exactly on time when the ring
    reaches them — the reference's rolling 60 s storage,
    Network.java:201-299, supports arbitrary horizons the same way) or are
    clamped into the ring and counted when ``spill_cap == 0``
    (`msg_discard_time` Network.java:36-40 is the sanctioned way to model
    bounded delivery windows).  Broadcast latencies are recomputed within
    the ring window and always clamp (counted in `clamped`) — a broadcast
    tail past the horizon needs a bigger ring, not spill.
  * Per-(node, ms) unicast deliveries beyond `inbox_cap` are counted in
    `NetState.dropped`; size the capacity for the protocol (tests assert 0).
  * Partition membership is evaluated at delivery time for broadcasts (the
    reference evaluates it at send time, Network.java:478); identical unless a
    partition changes while a message is in flight.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import prng
from .latency import full_latency, latency_floor_ms
from .protocol import FAR_FUTURE
from .state import EngineConfig, Inbox, NetState, Outbox

#: The engine's stages as `jax.named_scope`s.  A scope is compile-time
#: metadata (each op's `op_name`; no jaxpr equation), so a device trace
#: can be reduced by stage (`benchmark/stages.py`).  What no scope holds
#: (loop control, the copies XLA inserts for the scan carry) is the carry.
STAGE_INBOX = "engine.inbox"          # ring-row reads, delivery masks
STAGE_PROTOCOL = "protocol.step"      # the protocol's own step
STAGE_ROUTE = "engine.route"          # sender counters, latency, validity
STAGE_BIN = "engine.bin"              # slot clear, sort, rank, scatter


def _retire_broadcasts(cfg: EngineConfig, net: NetState, t) -> NetState:
    # A broadcast's last possible arrival is bc_time + horizon - 1.
    live = net.bc_active & ((t - net.bc_time) < cfg.horizon)
    return net.replace(bc_active=live)


def broadcast_arrivals(cfg: EngineConfig, model, net: NetState, nodes):
    """Per-(record, dest) broadcast arrival recompute — the one shared
    definition of the reference's stateless multicast-latency trick
    (Envelope.java:45-56, Network.java:493-503): latency is a pure function
    of (record seed, dest), never stored.  Returns ``(arrival [B, N],
    ok [B, N], clamped [B, N])`` where `ok` covers record-active, discard
    and partition checks (NOT the destination's down flag — delivery and
    introspection treat that differently) and `clamped` marks arrivals
    whose true latency outran the ring.
    """
    node_idx = jnp.arange(cfg.n, dtype=jnp.int32)
    delta = prng.uniform_delta(net.bc_seed[:, None], node_idx[None, :])
    lat = full_latency(model, nodes, net.bc_src[:, None], node_idx[None, :],
                       delta)
    # Discard is checked against the TRUE latency (Network.java:481 compares
    # nt before any storage), then the survivor is clamped into the ring.
    not_discarded = lat < cfg.msg_discard_time
    raw_lat = jnp.maximum(lat, 1)
    lat = jnp.clip(lat, 1, cfg.horizon - 2)
    arrival = net.bc_time[:, None] + 1 + lat
    ok = (net.bc_active[:, None] & not_discarded
          & (nodes.partition[net.bc_src][:, None] ==
             nodes.partition[None, :]))
    return arrival, ok, raw_lat != lat


def _bcast_inbox(cfg: EngineConfig, model, net: NetState, t):
    """Broadcast half of the time-t inbox: the per-(record, dest)
    arrival recompute of `broadcast_arrivals`, shaped for delivery.
    Returns ``(bc_data [N, B, F], bc_src [N, B], bc_size [N, B],
    bc_valid [N, B], n_clamped)``."""
    nodes = net.nodes
    n, b = cfg.n, cfg.bcast_slots
    arrival, bc_ok, clamped = broadcast_arrivals(cfg, model, net, nodes)
    bc_hit = bc_ok & (arrival == t) & (~nodes.down[None, :])     # [B, N]
    bc_valid = jnp.transpose(bc_hit)                             # [N, B]
    bc_data = jnp.broadcast_to(net.bc_payload[None, :, :],
                               (n, b, cfg.payload_words))
    bc_src = jnp.broadcast_to(net.bc_src[None, :], (n, b))
    bc_size = jnp.broadcast_to(net.bc_size[None, :], (n, b))
    # Broadcast deliveries whose true latency outran the ring (counted
    # once, at their clamped delivery ms).
    n_clamped = jnp.sum(bc_hit & clamped).astype(jnp.int32)
    return bc_data, bc_src, bc_size, bc_valid, n_clamped


def _unicast_inbox_window(cfg: EngineConfig, net: NetState, t, k: int):
    """Read K consecutive unicast inbox slices as ONE contiguous window.

    Requires ``t % k == 0`` with ``k`` dividing the horizon (so rows
    ``t % horizon .. t % horizon + k - 1`` never wrap) — the `step_kms`
    entry contract.  Returns ``(uc_data [K, N, C, F], uc_src [K, N, C],
    uc_size [K, N, C], uc_valid [K, N, C])`` with the same per-ms
    validity the per-ms slice computes (delivery-time down/partition
    checks are static across the window: `step_kms` requires a protocol
    that does not mutate liveness)."""
    nodes = net.nodes
    n, c, f = cfg.n, cfg.inbox_cap, cfg.payload_words
    p, ns = cfg.box_split, cfg.split_n
    h = t % cfg.horizon
    base = h * (ns * c)

    def rd(plane):
        return jax.lax.dynamic_slice(plane, (base,),
                                     (k * ns * c,)).reshape(k, ns, c)

    def rd_all(planes):
        if p == 1:
            return rd(planes[0])
        return jnp.concatenate([rd(pl) for pl in planes], axis=1)

    uc_data = jnp.stack(
        [rd_all(net.box_data[fi * p:(fi + 1) * p]) for fi in range(f)],
        axis=-1)                                    # [K, N, C, F]
    uc_src = rd_all(net.box_src)
    uc_size = rd_all(net.box_size)
    cnt = jax.lax.dynamic_slice(net.box_count, (h, 0), (k, n))   # [K, N]
    uc_valid = jnp.arange(c)[None, None, :] < cnt[:, :, None]
    deliver_ok = (~nodes.down[None, :, None]) & (
        nodes.partition[uc_src] == nodes.partition[None, :, None])
    return uc_data, uc_src, uc_size, uc_valid & deliver_ok


def build_inbox(cfg: EngineConfig, model, net: NetState, t):
    """Assemble the time-t inbox and bump receive counters.

    Mirrors the delivery path of Network.java:587-637: down destinations and
    cross-partition messages are silently dropped (:603-613), receive counters
    bumped per delivered message (:611-612).
    """
    nodes = net.nodes
    c, b, f = cfg.inbox_cap, cfg.bcast_slots, cfg.payload_words
    p, ns = cfg.box_split, cfg.split_n
    h = t % cfg.horizon

    # --- unicast slice: contiguous [Ns*C] window per sub-plane at
    # h*Ns*C, node-range sub-planes concatenated back to [N, C] ---
    base = h * (ns * c)

    def rd(plane):
        return jax.lax.dynamic_slice(plane, (base,),
                                     (ns * c,)).reshape(ns, c)

    def rd_all(planes):
        if p == 1:
            return rd(planes[0])
        return jnp.concatenate([rd(pl) for pl in planes], axis=0)

    uc_data = jnp.stack(
        [rd_all(net.box_data[fi * p:(fi + 1) * p]) for fi in range(f)],
        axis=-1)                                    # [N, C, F]
    uc_src = rd_all(net.box_src)
    uc_size = rd_all(net.box_size)
    uc_valid = jnp.arange(c)[None, :] < net.box_count[h][:, None]
    deliver_ok = (~nodes.down[:, None]) & (
        nodes.partition[uc_src] == nodes.partition[:, None])
    uc_valid = uc_valid & deliver_ok

    if b == 0:
        # Static no-broadcast path (protocols that never sendAll set
        # bcast_slots=0): no [B, N] latency recompute, and the inbox IS
        # the unicast slice — no concatenate materializing a copy.
        recv = jnp.sum(uc_valid, 1).astype(jnp.int32)
        rbytes = jnp.sum(jnp.where(uc_valid, uc_size, 0), 1).astype(
            jnp.int32)
        nodes = nodes.replace(msg_received=nodes.msg_received + recv,
                              bytes_received=nodes.bytes_received + rbytes)
        inbox = Inbox(data=uc_data, src=uc_src, valid=uc_valid)
        return inbox, nodes, jnp.asarray(0, jnp.int32)

    # --- broadcast recompute: which records arrive at exactly t? ---
    bc_data, bc_src, bc_size, bc_valid, n_clamped = _bcast_inbox(
        cfg, model, net, t)

    inbox = Inbox(
        data=jnp.concatenate([uc_data, bc_data], axis=1),
        src=jnp.concatenate([uc_src, bc_src], axis=1),
        valid=jnp.concatenate([uc_valid, bc_valid], axis=1),
    )

    recv = (jnp.sum(uc_valid, 1) + jnp.sum(bc_valid, 1)).astype(jnp.int32)
    rbytes = (jnp.sum(jnp.where(uc_valid, uc_size, 0), 1) +
              jnp.sum(jnp.where(bc_valid, bc_size, 0), 1)).astype(jnp.int32)
    nodes = nodes.replace(msg_received=nodes.msg_received + recv,
                          bytes_received=nodes.bytes_received + rbytes)
    return inbox, nodes, n_clamped


def _bin_into_ring(cfg: EngineConfig, net: NetState, t, src, dest, arrival,
                   payload, size, valid):
    """Scatter a batch of messages into the mailbox ring.

    A stable sort on (arrival, dest) bins messages into ring slots; rank
    within a (ms, dest) group + the current fill count gives each message
    its slot.  `dest` must already be clipped to [0, n); arrivals must lie
    within the ring: rel = arrival - t in [1, horizon-1] for the per-ms
    path, or [K, horizon + K - 2] for the fused `step_kms` path — rel >=
    horizon lands in one of the rows t % horizon .. t % horizon + K - 2,
    which is valid ONLY because step_kms clears all K consumed rows
    BEFORE binning (do not reorder).  Returns (net', n_dropped) —
    entries that found their (ms, dest) cell full.

    ``WTPU_PALLAS_ROUTE=1`` (or the serve plane's `route_kernel` knob)
    swaps the sort/scatter composition below for the fused Pallas
    routing megakernel (ops/pallas_route.py — bit-identical,
    tests/test_pallas_route.py; interpret mode on CPU).  The arrival
    contract above is exactly what makes the kernel's (row, dest)
    grouping coincide with the sort's (rel, dest) grouping: at most
    horizon-1 distinct rel values per batch, so rel % horizon is
    injective within it.
    """
    from ..ops.pallas_route import route_enabled
    if route_enabled():
        from ..ops.pallas_route import bin_into_ring_planes
        box_data, box_src, box_size, box_count, n_dropped = \
            bin_into_ring_planes(
                net.box_data, net.box_src, net.box_size, net.box_count,
                arrival % cfg.horizon, dest, src, size, payload, valid,
                horizon=cfg.horizon, cap=cfg.inbox_cap, n=cfg.n,
                split=cfg.box_split, payload_words=cfg.payload_words)
        return net.replace(box_data=box_data, box_src=box_src,
                           box_size=box_size, box_count=box_count), \
            n_dropped
    n, c = cfg.n, cfg.inbox_cap
    m = src.shape[0]
    rel = arrival - t
    # Two-pass stable radix sort on (rel, dest): avoids the int32 overflow a
    # fused `rel * n + dest` key would hit for n in the millions, yet still
    # yields one deterministic order with (rel, dest) groups contiguous.
    big = jnp.int32(0x7FFFFFFF)
    rel_k = jnp.where(valid, rel, big)
    dest_k = jnp.where(valid, dest, big)
    o1 = jnp.argsort(dest_k, stable=True)
    order = o1[jnp.argsort(rel_k[o1], stable=True)]
    rel_s, dest_s = rel_k[order], dest_k[order]
    idx = jnp.arange(m, dtype=jnp.int32)
    new_grp = ((rel_s != jnp.roll(rel_s, 1)) |
               (dest_s != jnp.roll(dest_s, 1))).at[0].set(True)
    rank = idx - jax.lax.cummax(jnp.where(new_grp, idx, 0))

    h_s = (arrival % cfg.horizon)[order]
    d_s = dest[order]
    ok_s = valid[order]
    slot = net.box_count[h_s, d_s] + rank
    ok_s = ok_s & (slot < c)

    # Flat 1-D scatters per node-range sub-plane (cell (h, d, slot) at
    # (h*Ns + d - j*Ns)*C + slot of sub-plane j = d // Ns); each
    # sub-plane's total size is the OOB sentinel for entries that belong
    # to another sub-plane or were dropped.
    p, ns = cfg.box_split, cfg.split_n
    f = cfg.payload_words
    payload_s = payload[order]
    src_s, size_s = src[order], size[order]
    box_data = list(net.box_data)
    box_src = list(net.box_src)
    box_size = list(net.box_size)
    sub_total = cfg.horizon * ns * c
    for j in range(p):
        dj = d_s - j * ns
        in_j = ok_s & (dj >= 0) & (dj < ns)
        flat_j = (h_s * ns + dj) * c + jnp.where(in_j, slot, 0)
        flat_jw = jnp.where(in_j, flat_j, sub_total)
        for fi in range(f):
            box_data[fi * p + j] = box_data[fi * p + j].at[flat_jw].set(
                payload_s[:, fi], mode="drop", unique_indices=True)
        box_src[j] = box_src[j].at[flat_jw].set(src_s, mode="drop",
                                                unique_indices=True)
        box_size[j] = box_size[j].at[flat_jw].set(size_s, mode="drop",
                                                  unique_indices=True)
    box_count = net.box_count.at[h_s, d_s].add(ok_s.astype(jnp.int32),
                                               mode="drop")
    n_dropped = jnp.sum(valid[order] & ~ok_s).astype(jnp.int32)
    return net.replace(box_data=tuple(box_data), box_src=tuple(box_src),
                       box_size=tuple(box_size), box_count=box_count), \
        n_dropped


def _alloc_free_slots(free, want):
    """Deterministic free-slot allocation for a fixed table: the i-th
    requester (in index order) takes the i-th free slot.  Returns
    ``(slot_w, ok)`` where slot_w == len(free) (an OOB sentinel for
    mode="drop" scatters) for requesters that found the table full."""
    cap = free.shape[0]
    rank = jnp.cumsum(want.astype(jnp.int32)) - 1
    n_free = jnp.sum(free).astype(jnp.int32)
    slot_order = jnp.argsort(~free, stable=True)        # free slots first
    ok = want & (rank < n_free)
    slot = slot_order[jnp.clip(rank, 0, cap - 1)]
    return jnp.where(ok, slot, cap), ok


def _park_in_spill(cfg: EngineConfig, net: NetState, src, dest, arrival,
                   payload, size, far):
    """Park far-future sends in the spill buffer (free slot = arrival < 0);
    overflow is counted in `sp_dropped`."""
    slot_w, ok = _alloc_free_slots(net.sp_arrival < 0, far)
    return net.replace(
        sp_arrival=net.sp_arrival.at[slot_w].set(arrival, mode="drop"),
        sp_src=net.sp_src.at[slot_w].set(src, mode="drop"),
        sp_dest=net.sp_dest.at[slot_w].set(dest, mode="drop"),
        sp_size=net.sp_size.at[slot_w].set(size, mode="drop"),
        sp_payload=net.sp_payload.at[slot_w].set(payload, mode="drop"),
        sp_dropped=net.sp_dropped + jnp.sum(far & ~ok).astype(jnp.int32))


def _drain_spill(cfg: EngineConfig, net: NetState, t):
    """Re-inject parked messages whose arrival is within ring reach.

    Entries parked by `enqueue_unicast` cross `arrival - t == horizon - 2`
    exactly once, but a restored/hand-built NetState (or a future horizon
    change) can hold entries already nearer than that — an exact-equality
    drain would leak them (never delivered, slot never freed).  Draining on
    <= with arrival clamped to t+1 (rel >= 1 for `_bin_into_ring`) is
    equivalent for the enqueue path and robust for any other state."""
    sel = (net.sp_arrival >= 0) & (net.sp_arrival - t <= cfg.horizon - 2)
    net2, n_drop = _bin_into_ring(cfg, net, t, net.sp_src, net.sp_dest,
                                  jnp.maximum(net.sp_arrival, t + 1),
                                  net.sp_payload, net.sp_size, sel)
    return net2.replace(
        sp_arrival=jnp.where(sel, -1, net2.sp_arrival),
        dropped=net2.dropped + n_drop)


def _route_unicast(cfg: EngineConfig, model, net: NetState, out: Outbox, t):
    """Shared unicast routing: sender counters, latency draws, validity.

    Returns ``(net', batch, abs_arrival_raw)`` where `batch` is the
    binnable tuple ``(src, dest_c, arrival, payload, size, valid, far)``
    — `arrival` already clamped into the ring relative to t, and
    `abs_arrival_raw` the unclamped absolute arrival (spill parking).

    The outbox may be NARROWER than cfg.out_deg (a contiguous slot window
    starting at out.slot0 — see Outbox.slot0): latency draws are keyed on
    the stable full-width slot id, so a narrow outbox whose live columns
    carry the same slot ids produces bit-identical arrivals while the
    binning sort runs over n * K_narrow entries instead of n * out_deg.
    """
    nodes = net.nodes
    n, k = cfg.n, out.dest.shape[1]
    m = n * k
    src = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    dest = out.dest.reshape(m)
    payload = out.payload.reshape(m, cfg.payload_words)
    size = out.size.reshape(m)
    delay = out.delay.reshape(m)

    want = (dest >= 0) & (~nodes.down[src])
    dest_c = jnp.clip(dest, 0, n - 1)

    # Attempted sends bump the sender's counters regardless of whether the
    # destination is reachable (Network.java:475-477 increments before the
    # partition/down checks).  src is repeat(arange(n), k), so the
    # scatter-add is just a per-row sum.
    sent = nodes.msg_sent + jnp.sum(
        want.reshape(n, k), axis=1, dtype=jnp.int32)
    sbytes = nodes.bytes_sent + jnp.sum(
        jnp.where(want, size, 0).reshape(n, k), axis=1, dtype=jnp.int32)
    nodes = nodes.replace(msg_sent=sent, bytes_sent=sbytes)
    net = net.replace(nodes=nodes)

    seed_t = prng.hash3(net.seed, prng.TAG_LATENCY, t)
    # Stable full-width slot id (== arange(m) for a full-width outbox).
    midx = src * cfg.out_deg + out.slot0 + \
        jnp.arange(m, dtype=jnp.int32) % k
    delta = prng.uniform_delta(seed_t, midx)
    lat = full_latency(model, nodes, src, dest_c, delta)
    not_discarded = lat < cfg.msg_discard_time
    # `delay` is sender-chosen scheduling (send-at-future-time,
    # sendArriveAt Network.java:384-390).  Arrivals past the ring either
    # park in the spill buffer (spill_cap > 0 — delivered exactly on time
    # when the ring reaches them) or are clamped to the ring edge and
    # counted in `net.clamped` (tests/harness assert clamped == 0).
    raw_total = jnp.clip(delay, 0, None) + jnp.maximum(lat, 1)
    total = jnp.clip(raw_total, 1, cfg.horizon - 2)
    valid = want & not_discarded & (~nodes.down[dest_c]) & (
        nodes.partition[src] == nodes.partition[dest_c])
    far = valid & (raw_total > cfg.horizon - 2)
    batch = (src, dest_c, t + 1 + total, payload, size, valid, far)
    return net, batch, t + 1 + raw_total


def enqueue_unicast(cfg: EngineConfig, model, net: NetState, out: Outbox, t):
    """Route the step's unicast sends into the mailbox ring.

    The reference creates one MessageArrival per destination with a fresh
    latency draw, sorts them, and links them into per-ms buckets
    (Network.java:449-487).  Here: one latency draw per message, then the
    sort-based binning of `_bin_into_ring`.
    """
    with jax.named_scope(STAGE_ROUTE):
        net, batch, arrival_raw = _route_unicast(cfg, model, net, out, t)
    src, dest_c, arrival, payload, size, valid, far = batch
    with jax.named_scope(STAGE_BIN):
        if cfg.spill_cap > 0:
            net = _park_in_spill(cfg, net, src, dest_c, arrival_raw,
                                 payload, size, far)
            ring_valid = valid & ~far
            n_clamped = jnp.asarray(0, jnp.int32)
        else:
            ring_valid = valid
            n_clamped = jnp.sum(far).astype(jnp.int32)

        net, n_dropped = _bin_into_ring(cfg, net, t, src, dest_c, arrival,
                                        payload, size, ring_valid)
    return net.replace(dropped=net.dropped + n_dropped,
                       clamped=net.clamped + n_clamped)


def enqueue_broadcast(cfg: EngineConfig, net: NetState, out: Outbox, t):
    """Allocate broadcast-table slots for this step's sendAll requests."""
    nodes = net.nodes
    n = cfg.n
    req = out.bcast & (~nodes.down)

    # sendAll counts one attempted send per destination (all N nodes,
    # including self — Network.java:341-347 sends to allNodes).
    sent = nodes.msg_sent + jnp.where(req, n, 0).astype(jnp.int32)
    sbytes = nodes.bytes_sent + jnp.where(req, out.bcast_size * n, 0)
    nodes = nodes.replace(msg_sent=sent, bytes_sent=sbytes)

    slot_w, ok = _alloc_free_slots(~net.bc_active, req)

    node_idx = jnp.arange(n, dtype=jnp.int32)
    bseed = prng.hash3(prng.hash2(net.seed, prng.TAG_BCAST),
                       jnp.full((n,), t, jnp.int32), node_idx)
    return net.replace(
        nodes=nodes,
        bc_active=net.bc_active.at[slot_w].set(True, mode="drop"),
        bc_src=net.bc_src.at[slot_w].set(node_idx, mode="drop"),
        bc_time=net.bc_time.at[slot_w].set(t, mode="drop"),
        bc_payload=net.bc_payload.at[slot_w].set(out.bcast_payload,
                                                 mode="drop"),
        bc_size=net.bc_size.at[slot_w].set(out.bcast_size, mode="drop"),
        bc_seed=net.bc_seed.at[slot_w].set(bseed.astype(jnp.int32),
                                           mode="drop"),
        bc_dropped=net.bc_dropped + jnp.sum(req & ~ok).astype(jnp.int32),
    )


def step_ms(protocol, net: NetState, pstate, hints=None, tap=None):
    """Advance the simulation by exactly one millisecond (pure, jittable).

    `hints` is an optional static phase-hint dict (see `scan_chunk`): when
    the protocol's task schedule is statically known, it tells the step
    which masked sub-computations cannot fire this ms so they are never
    traced at all — the tensor analogue of the reference's empty-ms
    skip in nextMessage (Network.java:533-570), where a ms with no events
    costs nothing.

    `tap` is the trace plane's observation hook (wittgenstein_tpu/obs/
    trace.py): a callable invoked twice per simulated ms during TRACING —
    ``tap(t, net, None)`` at ms entry (before retire/drain/delivery, so
    the tap can read the ms's ring row, spill drain set and pre-retire
    broadcast table as pure functions of the carried state) and
    ``tap(t, net, out)`` right after the protocol step (the outbox is the
    only per-message send information that never reaches the state).  The
    default ``tap=None`` traces ZERO extra operations — the uninstrumented
    program is bit-for-bit the historical one (the `trace_zero_cost` /
    `metrics_zero_cost` lints pin its carry width and op count).
    """
    cfg, model = protocol.cfg, protocol.latency
    t = net.time
    # Chaos-plane hook (wittgenstein_tpu/chaos): churn/partition state
    # is a stateless function of t, written at every ms entry BEFORE
    # anything observes or delivers — the tap then sees exactly the
    # liveness the engine runs under.  Protocols without the hook trace
    # zero extra operations (the zero-cost lints stay pinned).
    af = getattr(protocol, "apply_faults", None)
    if af is not None:
        with jax.named_scope(STAGE_INBOX):
            net = af(net, t)
    if tap is not None:
        tap(t, net, None)
    with jax.named_scope(STAGE_INBOX):
        if cfg.bcast_slots > 0:
            net = _retire_broadcasts(cfg, net, t)
        if cfg.spill_cap > 0:
            net = _drain_spill(cfg, net, t)
        inbox, nodes, bc_clamped = build_inbox(cfg, model, net, t)
        net = net.replace(nodes=nodes, clamped=net.clamped + bc_clamped)

    key = jax.random.fold_in(jax.random.PRNGKey(net.seed), t)
    with jax.named_scope(STAGE_PROTOCOL):
        if hints is None:
            pstate, nodes, out = protocol.step(pstate, net.nodes, inbox, t,
                                               key)
        else:
            pstate, nodes, out = protocol.step(pstate, net.nodes, inbox, t,
                                               key, hints=hints)
    net = net.replace(nodes=nodes)
    if tap is not None:
        tap(t, net, out)

    # Clear the consumed slot, then route new sends (their arrivals are
    # >= t+2, so they can never land in the slot just cleared).
    with jax.named_scope(STAGE_BIN):
        net = net.replace(box_count=net.box_count.at[t % cfg.horizon].set(0))
    net = enqueue_unicast(cfg, model, net, out, t)
    if cfg.bcast_slots > 0:
        with jax.named_scope(STAGE_ROUTE):
            net = enqueue_broadcast(cfg, net, out, t)
    return net.replace(time=t + 1), pstate


def step_kms(protocol, net: NetState, pstate, k: int, hints_k=None,
             tap=None):
    """Advance K milliseconds in one fused engine pass — the superstep.

    Bit-identical to K `step_ms` calls (tests/test_superstep.py) whenever
    the latency model provably never delivers a unicast in fewer than
    ``F = latency_floor_ms()`` milliseconds and ``K <= F + 1`` (the
    classic lookahead/conservative-window argument from parallel DES): a
    unicast sent at window ms t+i arrives no earlier than t+i+1+F >=
    t+K, so nothing produced inside the window can be consumed inside
    the window.  Self-sends bypass the model (full_latency pins
    src == dst to 1 ms), so a floor above 1 is only usable for protocols
    that declare ``may_self_send = False``.  That licenses:

      * all K unicast inbox slices read up-front as ONE contiguous
        K-row window (`_unicast_inbox_window`);
      * ONE sort-based binning over all K outboxes (keyed on
        (rel, dest) with rel relative to t, spanning [K, horizon+K-2];
        batch order inside a (ms, dest) cell equals the sequential
        order the per-ms path produces, so slots are identical);
      * all K consumed ring slots cleared with one K-row update.

    This cuts the engine's per-ms fixed cost (sorts, scatter passes,
    slices, clears) — the op-latency-bound regime's dominant term
    (BENCH_NOTES.md r3) — by ~K/2x over the historical 2-ms fusion.

    Broadcasts are NOT window-fused: their table evolves and their
    arrivals are recomputed per-ms-exactly inside the window
    (retire(t+i) -> deliver(t+i) -> step -> enqueue(t+i)), because a
    sendAll reaches its own sender in 1 ms and would otherwise land
    inside any K > 2 window.  The broadcast recompute is elementwise
    [B, N] work — none of the sort/scatter fixed cost being amortized —
    so per-ms exactness there costs nothing extra.

    Requirements (enforced by `check_chunk_config`): spill_cap == 0,
    K divides the horizon, entry time ≡ 0 (mod K), K <= floor + 1 via
    `unicast_floor_ms`, and a protocol that does not mutate liveness.

    `tap` is the trace plane's observation hook (see `step_ms`): it
    fires per SIMULATED ms inside the window — entry tap before each
    ms's broadcast retire, post tap right after its protocol step — so
    every recorded event carries its exact origin ms, never the window
    start (K-vs-1 trace equality pinned in tests/test_trace.py).
    """
    if hints_k is not None and len(hints_k) != k:
        raise ValueError(f"hints_k must have {k} entries, got "
                         f"{len(hints_k)}")
    if k == 1:
        return step_ms(protocol, net, pstate,
                       hints=None if hints_k is None else hints_k[0],
                       tap=tap)
    cfg, model = protocol.cfg, protocol.latency
    if cfg.spill_cap > 0:
        raise ValueError("step_kms requires spill_cap == 0 (spill drain "
                         "is inherently per-ms)")
    t = net.time
    # Chaos-plane hook: ONE stateless application per window.  Sound
    # because `check_chunk_config` requires every churn/partition
    # transition to be K-aligned, so the fault state is constant across
    # the window — each in-window ms (inbox validity, routing validity,
    # taps) sees exactly what the per-ms engine would.
    af = getattr(protocol, "apply_faults", None)
    if af is not None:
        with jax.named_scope(STAGE_INBOX):
            net = af(net, t)
    # Entry tap for the window's FIRST ms: before retire, matching the
    # per-ms path's observation point.  Later ms tap inside the loop —
    # their ring rows are untouched until the window's deferred clear,
    # and in-window sends arrive >= t+K (the window soundness proof),
    # so each per-ms entry observation reads exactly the state the
    # per-ms engine would show it (tests/test_trace.py pins the K-vs-1
    # trace equality).
    if tap is not None:
        tap(t, net, None)
    with jax.named_scope(STAGE_INBOX):
        if cfg.bcast_slots > 0:
            net = _retire_broadcasts(cfg, net, t)

        # All K unicast slices + their receive counters up-front
        # (counters are write-only to the protocol step, so the early
        # bump is unobservable — the step_2ms precedent).
        uc_data, uc_src, uc_size, uc_valid = _unicast_inbox_window(
            cfg, net, t, k)
        recv = jnp.sum(uc_valid, axis=(0, 2)).astype(jnp.int32)
        rbytes = jnp.sum(jnp.where(uc_valid, uc_size, 0),
                         axis=(0, 2)).astype(jnp.int32)
        net = net.replace(nodes=net.nodes.replace(
            msg_received=net.nodes.msg_received + recv,
            bytes_received=net.nodes.bytes_received + rbytes))

    outs = []
    for i in range(k):
        ti = t + i if i else t      # no dead `t + 0` eqn in the trace
        if i > 0 and tap is not None:
            tap(ti, net, None)
        with jax.named_scope(STAGE_INBOX):
            if i > 0 and cfg.bcast_slots > 0:
                net = _retire_broadcasts(cfg, net, ti)
            if cfg.bcast_slots > 0:
                bc_data, bc_src, bc_size, bc_valid, n_cl = _bcast_inbox(
                    cfg, model, net, ti)
                recv_b = jnp.sum(bc_valid, 1).astype(jnp.int32)
                rb_b = jnp.sum(jnp.where(bc_valid, bc_size, 0),
                               1).astype(jnp.int32)
                net = net.replace(
                    nodes=net.nodes.replace(
                        msg_received=net.nodes.msg_received + recv_b,
                        bytes_received=net.nodes.bytes_received + rb_b),
                    clamped=net.clamped + n_cl)
                inbox = Inbox(
                    data=jnp.concatenate([uc_data[i], bc_data], axis=1),
                    src=jnp.concatenate([uc_src[i], bc_src], axis=1),
                    valid=jnp.concatenate([uc_valid[i], bc_valid], axis=1))
            else:
                inbox = Inbox(data=uc_data[i], src=uc_src[i],
                              valid=uc_valid[i])
        key = jax.random.fold_in(jax.random.PRNGKey(net.seed), ti)
        h_i = None if hints_k is None else hints_k[i]
        with jax.named_scope(STAGE_PROTOCOL):
            if h_i is None:
                pstate, nodes, out = protocol.step(pstate, net.nodes,
                                                   inbox, ti, key)
            else:
                pstate, nodes, out = protocol.step(pstate, net.nodes,
                                                   inbox, ti, key,
                                                   hints=h_i)
        net = net.replace(nodes=nodes)
        outs.append(out)
        if tap is not None:
            tap(ti, net, out)
        if cfg.bcast_slots > 0:
            with jax.named_scope(STAGE_ROUTE):
                net = enqueue_broadcast(cfg, net, out, ti)

    # Clear all K consumed slots in one K-row window (h ≡ 0 mod K and
    # K | horizon: no wrap).
    h = t % cfg.horizon
    with jax.named_scope(STAGE_BIN):
        net = net.replace(box_count=jax.lax.dynamic_update_slice(
            net.box_count, jnp.zeros((k, cfg.n), jnp.int32), (h, 0)))

    # Route every outbox (latency draws keyed on each step's own ms),
    # then bin them together: one sort + one scatter pass for K ms.
    with jax.named_scope(STAGE_ROUTE):
        batches = []
        for i, out in enumerate(outs):
            net, b, _ = _route_unicast(cfg, model, net, out,
                                       t + i if i else t)
            batches.append(b)
        terms = [jnp.sum(b[6]) for b in batches]
        n_clamped = terms[0]
        for tm in terms[1:]:
            n_clamped = n_clamped + tm
        n_clamped = n_clamped.astype(jnp.int32)
    with jax.named_scope(STAGE_BIN):
        src = jnp.concatenate([b[0] for b in batches])
        dest = jnp.concatenate([b[1] for b in batches])
        arrival = jnp.concatenate([b[2] for b in batches])
        payload = jnp.concatenate([b[3] for b in batches])
        size = jnp.concatenate([b[4] for b in batches])
        valid = jnp.concatenate([b[5] for b in batches])
        net, n_dropped = _bin_into_ring(cfg, net, t, src, dest, arrival,
                                        payload, size, valid)
    net = net.replace(dropped=net.dropped + n_dropped,
                      clamped=net.clamped + n_clamped)
    return net.replace(time=t + k), pstate


def step_2ms(protocol, net: NetState, pstate, hints2=(None, None)):
    """Advance TWO milliseconds in one fused engine pass — the K == 2
    superstep (`step_kms`), kept as a named entry point because K == 2
    is the universally-valid fusion: the engine's minimum latency of
    1 ms is itself the floor (a send at t arrives no earlier than t+2),
    so no latency-model floor and no self-send declaration is needed.
    """
    return step_kms(protocol, net, pstate, 2, hints_k=list(hints2))


def unicast_floor_ms(protocol) -> int:
    """The provable lower bound on any of this protocol's unicast
    delivery latencies — the term that licenses a K-ms superstep window
    (K <= floor + 1, `step_kms`).

    `full_latency` pins src == dst sends to 1 ms REGARDLESS of the
    latency model, so the model's `latency_floor_ms` only applies to
    protocols that declare ``may_self_send = False`` (an audited promise
    that step() never emits a unicast with dest == src).  The default —
    no declaration — is the conservative 1: every protocol then still
    gets the universally-valid K == 2 fusion, never an unsound K."""
    if getattr(protocol, "may_self_send", True):
        return 1
    return latency_floor_ms(protocol.latency)


def superstep_ok(protocol, superstep: int = 2) -> bool:
    """True iff `step_kms` with this K is valid for this protocol (the
    chunk length and entry time must additionally be K-aligned —
    per-call properties the caller checks).  The single shared
    eligibility predicate: scan_chunk raises on violations,
    Runner/harness demote to the largest valid K (`pick_superstep`)."""
    cfg = protocol.cfg
    sched = getattr(protocol, "chaos_schedule", None)
    return (cfg.spill_cap == 0
            and superstep >= 1
            and cfg.horizon % superstep == 0
            and superstep < cfg.horizon
            and superstep <= unicast_floor_ms(protocol) + 1
            and not getattr(protocol, "mutates_liveness", False)
            and (sched is None or sched.superstep_aligned(superstep)))


def fast_forward_ok(protocol) -> bool:
    """True iff the quiet-window fast-forward path is worth taking for
    this protocol: spill-free (the spill drain is inherently per-ms,
    same constraint as `superstep_ok`) and the protocol implements the
    `next_action_time` oracle half (core/protocol.py).  Without the
    method `fast_forward_chunk` is still SOUND — the engine then treats
    every ms as active — but it never jumps, so callers gate on this."""
    return (protocol.cfg.spill_cap == 0 and
            getattr(protocol, "next_action_time", None) is not None)


def check_chunk_config(protocol, ms, t0_mod=None, superstep=1,
                       fast_forward=False):
    """The shared eligibility gate for the engine chunk variants — plain
    scan, fused superstep-K, phase-specialized, fast-forward.
    `scan_chunk` and the fast-forward builders (including the batched
    ones) route through it so each shared constraint and its remedy are
    stated in one place; the batched engine layers its own narrower
    preconditions (broadcast-free) on top.  The gate RAISES — it never
    silently changes results; drivers that want automatic demotion pick
    through `pick_superstep` before building.

    One obligation is structurally out of the gate's reach: the chunk
    builder never sees the ABSOLUTE entry time, so with superstep K it
    can verify K-alignment only as far as `t0_mod` (a residue mod the
    schedule lcm) pins it — completely when K | lcm, only mod
    gcd(K, lcm) otherwise, and not at all without phase specialization.
    Entering a superstep-K chunk at a time that is not a multiple of K
    is a CONTRACT VIOLATION the compiled window cannot detect (the
    K-row ring reads/clears land on the wrong rows); callers that know
    t0 must route through `pick_superstep(t0=...)`, which checks the
    absolute alignment (all in-tree drivers do)."""
    cfg = protocol.cfg
    if not isinstance(superstep, int) or superstep < 1:
        raise ValueError(f"superstep must be a positive int, got "
                         f"{superstep!r}")
    if fast_forward:
        if t0_mod is not None:
            raise ValueError(
                "fast_forward is incompatible with phase-specialized "
                "scans (t0_mod): phase hints statically specialize each "
                "ms of an unrolled schedule period, while fast-forward "
                "jumps the clock dynamically — the hint<->time pairing "
                "cannot survive a data-dependent jump. Drop t0_mod (the "
                "oracle already skips the hint-masked quiet ms, "
                "including data-dependent ones hints cannot see)")
        if cfg.spill_cap > 0:
            raise ValueError(
                f"fast_forward requires spill_cap == 0 (got "
                f"{cfg.spill_cap}): the spill drain re-examines the "
                "buffer every ms, so a skipped window could miss a "
                "re-injection. Use a horizon that covers the latency "
                "tail instead of spill, or run without fast_forward")
    if superstep >= 2:
        k = superstep
        even = "an even" if k == 2 else f"a multiple-of-{k}"
        if cfg.spill_cap > 0:
            raise ValueError(
                f"superstep={k} needs spill_cap == 0 (got "
                f"{cfg.spill_cap}): the spill drain is inherently "
                "per-ms. Fix: size the horizon for the latency tail "
                "instead of spill, or fall back to superstep=1")
        if getattr(protocol, "mutates_liveness", False):
            raise ValueError(
                f"superstep={k} needs a protocol whose step() does not "
                "mutate node liveness (every inbox validity check in the "
                "window is evaluated against window-entry down/partition "
                "state). Fix: superstep=1 for this protocol")
        if cfg.horizon % k or k >= cfg.horizon:
            raise ValueError(
                f"superstep={k} needs K to divide the horizon with room "
                f"to spare (horizon {cfg.horizon}): the K consumed ring "
                "rows are read and cleared as one contiguous window. "
                f"Fix: pad the horizon to a multiple of {k} (at least "
                f"{2 * k}), or lower K")
        sched = getattr(protocol, "chaos_schedule", None)
        if sched is not None and not sched.superstep_aligned(k):
            bad = [t for t in sched.transition_times() if t % k]
            raise ValueError(
                f"superstep={k} needs every chaos churn/partition "
                f"transition on a K-ms window boundary (misaligned: "
                f"{bad[:8]}): liveness/partition state is applied at "
                "window entry, so a mid-window transition would be "
                "visible to the per-ms engine but not the fused window. "
                f"Fix: align the FaultSchedule times to multiples of "
                f"{k}, pick a superstep dividing "
                f"gcd={sched.align_gcd() or 1} of the transition times, "
                "or fall back to superstep=1")
        floor = unicast_floor_ms(protocol)
        if k > floor + 1:
            self_send = getattr(protocol, "may_self_send", True)
            why = (
                "the protocol has not declared may_self_send = False, "
                "and a self-addressed unicast always arrives in exactly "
                "1 ms (full_latency pins src == dst), so only the "
                "universal K = 2 window is provable"
                if self_send else
                f"{protocol.latency!r} proves latency_floor_ms() = "
                f"{floor}, and a unicast sent at the window's first ms "
                f"can arrive {floor + 1} ms later — inside any window "
                f"longer than {floor + 1}")
            raise ValueError(
                f"superstep={k} exceeds the provable quiet window: {why}."
                f" Fix: use superstep <= {floor + 1}, switch to a latency"
                " model with a floor >= K-1 ms (e.g. NetworkFixedLatency,"
                " EthScanNetworkLatency), or — if step() provably never "
                "emits a unicast with dest == src — declare "
                "may_self_send = False on the protocol")
        if ms % k:
            raise ValueError(
                f"superstep={k} needs {even} chunk (got {ms}): the scan "
                f"advances in fused {k}-ms windows. Fix: make the chunk "
                f"length a multiple of {k}, or fall back to a smaller "
                "superstep for this chunk")
        if t0_mod is not None:
            # `t0_mod` is a residue mod the schedule lcm, so it pins the
            # absolute entry time only mod gcd(K, lcm) — that is the
            # provable part.  When K | lcm the check is complete
            # (t0 % K == t0_mod % K); otherwise K-alignment of the
            # ABSOLUTE entry time cannot be decided from t0_mod at all
            # and remains the caller's contract (`pick_superstep` sees
            # the real t0 and verifies it for the in-tree drivers; a
            # misaligned entry would make the K-row ring window
            # read/clear the wrong rows with no runtime error).
            import math
            sched = getattr(protocol, "schedule_lcm", None)
            g = math.gcd(k, sched) if sched else k
            if t0_mod % g:
                raise ValueError(
                    f"superstep={k} needs {even} entry time "
                    f"(t0_mod={t0_mod} is not 0 mod "
                    f"gcd(K, schedule_lcm)={g}, so NO absolute entry "
                    f"time can satisfy both time % lcm == {t0_mod} and "
                    f"the K-aligned window contract): the window's ring "
                    "rows are read as one K-aligned block. Fix: enter "
                    "on a K-aligned chunk boundary (in-tree drivers "
                    f"start at time 0 and use multiple-of-{k} chunks; "
                    "burn one unaligned superstep=1 chunk first to "
                    "realign), or keep superstep=1 for this chunk. "
                    "(allow_unaligned only relaxes the schedule-lcm "
                    "length check, not entry alignment — it cannot fix "
                    "this one.)")


class PhasePlan(NamedTuple):
    """One schedule block of a phase-specialized K-ms scan (see
    `phase_plan`)."""
    block: int      # ms per block: the K-adjusted schedule lcm
    steps: tuple    # the block's block // K supersteps: K hints each

    @property
    def lead(self) -> tuple:
        """The block's first superstep: its K hints as they are."""
        return self.steps[0]

    @property
    def rest(self) -> tuple:
        """K hints OR-merged over the block's other supersteps."""
        return tuple(_or_hints(slot) for slot in zip(*self.steps[1:]))

    @property
    def repeats(self) -> int:
        """Supersteps after the lead: block // K - 1."""
        return len(self.steps) - 1


def _or_hints(hints) -> dict:
    """Per-key OR of hint dicts; a key a dict lacks counts as True (the
    protocol keeps that gate dynamic), so the merge is sound for every
    ms it stands for."""
    keys = set().union(*hints)
    return {key: any(h.get(key, True) for h in hints) for key in keys}


def phase_plan(protocol, t0_mod: int, superstep: int):
    """The static schedule of a phase-specialized K-ms scan entered at
    ``time % schedule_lcm == t0_mod``: blocks of `block` ms (the lcm
    grown to a multiple of K), each `steps` of K per-ms hints.
    `scan_chunk` unrolls the steps; `scan_chunk_batched` runs the
    compact form, the `lead` superstep once, then `repeats` supersteps
    of one `rest` body, whose hints are the per-slot OR of the block's
    other supersteps.  A hint of True keeps the step's dynamic gate, so
    the merged body is exact on every superstep it stands for, and the
    block traces two superstep bodies instead of block // K.  None when
    the protocol declares no static schedule."""
    lcm = getattr(protocol, "schedule_lcm", None)
    if not lcm:
        return None
    import math
    k = superstep
    block = lcm * k // math.gcd(lcm, k)
    hints = [protocol.phase_hints((t0_mod + dt) % lcm)
             for dt in range(block)]
    return PhasePlan(block, tuple(tuple(hints[i:i + k])
                                  for i in range(0, block, k)))


#: Default upper bound for auto-picked superstep windows: past ~32 the
#: amortized fixed cost is already < 1/32 of its per-ms value while the
#: unrolled window body keeps growing compile time linearly.
AUTO_SUPERSTEP_MAX = 32


def pick_superstep(protocol, ms, t0=None, max_k: int = AUTO_SUPERSTEP_MAX,
                   also_divides=None, lcm=None) -> int:
    """The largest K for which `step_kms` is provably exact for chunks
    of `ms` entered at absolute time `t0` (and every later boundary
    ``t0 + j*ms`` — `ms % K == 0` keeps the alignment invariant across
    chunk reuse).  ``t0=None`` (entry time unknown, e.g. a traced
    value) conservatively returns 1.  `also_divides` adds a caller
    divisibility constraint (the obs interval: a K window must never
    straddle a `stat_each_ms` row); `lcm` adds the phase-specialized
    scan's constraints (chunk a multiple of the K-adjusted schedule
    lcm, K-aligned entry phase).  Never raises — this is the demotion
    half of the gate; `check_chunk_config` is the raising half."""
    import math

    ms = int(ms)
    best = 1
    for k in range(2, min(int(max_k), ms) + 1):
        if ms % k or (t0 is None or int(t0) % k):
            continue
        if also_divides is not None and also_divides % k:
            continue
        if lcm:
            # Only the chunk length constrains the phase-specialized
            # scan: its hint block spans lcm_k and k | lcm_k, so the
            # `t0 % k == 0` check above already K-aligns every window
            # start regardless of the entry's schedule residue (a
            # residue-based re-check here would demote K=8 for e.g.
            # t0=24, lcm=20 — a perfectly valid aligned entry).
            lcm_k = lcm * k // math.gcd(lcm, k)
            if ms % lcm_k:
                continue
        if superstep_ok(protocol, k):
            best = k
    return best


def next_work(protocol, net: NetState, pstate, t):
    """The next-event oracle: the earliest absolute ms >= t that can
    contain work, computed entirely on-device.  Min over

      (a) the next nonempty mailbox ring row — `box_count` is indexed by
          absolute-time-mod-horizon, and with ``spill_cap == 0`` every
          in-flight unicast lives in the ring, so a row with a nonzero
          count IS a pending delivery at ``t + ((row - t) % horizon)``;
      (b) the earliest live broadcast arrival >= t — recomputed exactly
          per (record, dest), the same stateless-latency trick as
          delivery (`broadcast_arrivals`); conservative only in keeping
          arrivals to down/irrelevant destinations (an under-jump, never
          an over-jump);
      (c) the protocol's `next_action_time(pstate, nodes, t)` timers —
          protocols without the method declare every ms active.

    Soundness contract (tests/test_fast_forward.py): every ms in
    ``[t, next_work)`` is bit-identical to a no-op step — empty inbox,
    no timer, `protocol.step` is the identity and emits nothing — so
    `fast_forward_chunk` may jump straight to the returned time.
    """
    cfg, model = protocol.cfg, protocol.latency
    far = jnp.int32(FAR_FUTURE)
    rows = jnp.arange(cfg.horizon, dtype=jnp.int32)
    row_any = jnp.any(net.box_count > 0, axis=-1)            # [H]
    nxt = jnp.min(jnp.where(row_any, t + (rows - t) % cfg.horizon, far))
    if cfg.bcast_slots > 0:
        # NOT redundant with the recompute build_inbox did this ms: the
        # oracle runs on the POST-step table — the step may have
        # enqueued new broadcasts or retired old ones, and reusing the
        # pre-step arrivals could miss a new record's arrival and
        # over-jump (the one failure mode the contract forbids).
        arrival, ok, _ = broadcast_arrivals(cfg, model, net, net.nodes)
        nxt = jnp.minimum(
            nxt, jnp.min(jnp.where(ok & (arrival >= t), arrival, far)))
    nat = getattr(protocol, "next_action_time", None)
    proto_next = t if nat is None else nat(pstate, net.nodes, t)
    return jnp.maximum(jnp.minimum(nxt, proto_next), t).astype(jnp.int32)


def _jump(cfg: EngineConfig, net: NetState, dt, t2):
    """Fast-forward `dt` provably-quiet milliseconds to absolute time
    `t2` in one hop.  Only time-translation-trivial state moves: the
    clock (which IS the ring head — rows are indexed by time % horizon,
    and every skipped row is empty by the oracle's guarantee) and
    broadcast retirement.  Retirement must match the per-ms path
    bit-for-bit: after per-ms steps t..t2-1 the last retire ran at
    t2-1, and retirement is monotone in t, so one retire at t2-1
    reproduces the whole sequence (idempotent when dt == 0)."""
    if cfg.bcast_slots > 0:
        net = net.replace(bc_active=net.bc_active &
                          ((t2 - 1 - net.bc_time) < cfg.horizon))
    return net.replace(time=net.time + dt)


def fast_forward_chunk(protocol, ms: int, seed_axis: bool = False,
                       superstep: int = 1):
    """Quiet-window fast-forwarding chunk: advance exactly `ms`
    simulated milliseconds as one `lax.while_loop` that runs a full
    `step_ms` body only on milliseconds that can contain work and jumps
    the clock by ``next_work - t`` across provably-quiet windows — the
    compiled-engine recovery of the reference's event-driven main loop
    (Network.java receiveUntil/nextMessage :533-637), which never pays
    for an empty ms.  Bit-identical to the per-ms `scan_chunk`
    (tests/test_fast_forward.py) because a skipped ms is exactly a
    no-op step body.

    ``seed_axis=True`` operates on vmap-batched state (leading [R] axis
    on every leaf, lockstep times — the bench/harness batch layout):
    ONE while loop whose body vmaps `step_ms` over the batch and jumps
    by the MIN of the per-seed oracles, so the whole batch stays in
    lockstep and a window is skipped only when every seed is quiet.

    Returns ``run(net, pstate) -> (net, pstate, stats)`` with
    ``stats = {"skipped_ms": int32, "jump_count": int32}`` — the skip
    accounting that makes a fast-forward speedup attributable
    (`bench.py` reports both).  `scan_chunk(fast_forward=True)` wraps
    this and drops the stats for interface-compatible callers.

    ``superstep=K`` runs the loop body as one fused `step_kms` window
    (jump to the next work, then advance in K-aligned supersteps): jump
    offsets are floored to multiples of K so every loop entry satisfies
    the superstep's alignment contract — an unaligned oracle target
    lands up to K-1 quiet ms early, which is sound (those ms are no-op
    steps the window simply executes).
    """
    check_chunk_config(protocol, ms, superstep=superstep,
                       fast_forward=True)
    cfg, k = protocol.cfg, superstep

    def run(net, pstate):
        t0 = net.time[0] if seed_axis else net.time
        t_end = t0 + ms

        def cond(carry):
            t = carry[0].time[0] if seed_axis else carry[0].time
            return t < t_end

        def body(carry):
            net, ps, skipped, jumps = carry
            if seed_axis:
                net, ps = jax.vmap(
                    lambda n_, p_: step_kms(protocol, n_, p_, k))(net, ps)
                t1 = net.time[0]
                nw = jnp.min(jax.vmap(
                    lambda n_, p_: next_work(protocol, n_, p_, t1))(
                    net, ps))
            else:
                net, ps = step_kms(protocol, net, ps, k)
                t1 = net.time
                nw = next_work(protocol, net, ps, t1)
            dt = jnp.clip(nw, t1, t_end) - t1
            if k > 1:
                dt = dt - dt % k          # keep entry times K-aligned
            net = _jump(cfg, net, dt, t1 + dt)
            return (net, ps, skipped + dt,
                    jumps + (dt > 0).astype(jnp.int32))

        z = jnp.asarray(0, jnp.int32)
        net, pstate, skipped, jumps = jax.lax.while_loop(
            cond, body, (net, pstate, z, z))
        return net, pstate, {"skipped_ms": skipped, "jump_count": jumps}

    return run


def scan_chunk(protocol, ms: int, t0_mod=None, allow_unaligned=False,
               superstep: int = 1, fast_forward: bool = False):
    """Returns ``run(net, pstate) -> (net, pstate)`` advancing `ms`
    milliseconds as one `lax.scan` — the single shared chunk body used by
    `Runner`, the harness, and the sharded runner.

    Phase specialization: protocols whose task schedule is statically
    known (no desynchronized start, constant node speed) expose
    ``schedule_lcm`` (the ms period after which the schedule repeats) and
    ``phase_hints(tmod)`` (which masked sub-computations can fire at
    ``time % lcm == tmod``).  Passing ``t0_mod`` (= entry ``net.time %
    lcm``, usually 0) then scans over lcm-sized blocks whose body UNROLLS
    one schedule period with per-ms static hints, so e.g. Handel's
    [N, Q, W] verification scoring is only traced on the
    1-in-pairing_time ms where any node can verify — the reference's own
    empty-ms skip (Network.java:533-570), recovered under jit.  (An
    earlier design dispatched each ms through ``lax.switch`` over the
    distinct hint variants — much cheaper to compile, but conditionals
    block XLA's in-place buffer aliasing, and copying the full simulator
    carry per ms cost far more than the skipped work saved; the unrolled
    block keeps every step inlined and alias-friendly.)  Results are
    bit-identical to the plain path (tests/test_phase_hints.py); callers
    must enter with ``net.time % schedule_lcm == t0_mod``.

    Nearly every caller REUSES the returned function for consecutive
    chunks, which keeps the alignment invariant only when ``ms`` is a
    multiple of the lcm — so that is enforced here (the one central
    guard; a config change that alters the lcm then fails loudly instead
    of silently dispatching the wrong phases from the second chunk on).
    A deliberately unaligned one-shot chunk may pass
    ``allow_unaligned=True`` (the sub-lcm tail is unrolled after the
    block scan); the next chunk's t0_mod is then ``(t0_mod + ms) % lcm``.

    ``superstep=K`` advances in fused K-ms engine windows (`step_kms` —
    bit-identical, tests/test_superstep.py) when the K-aware gate
    (`check_chunk_config`) proves the window: K <= the protocol's
    unicast latency floor + 1, K | horizon, K | chunk, K-aligned entry.

    ``fast_forward=True`` swaps the dense scan for the quiet-window
    `lax.while_loop` engine (`fast_forward_chunk` — bit-identical,
    tests/test_fast_forward.py), dropping the skip statistics; callers
    that want them use `fast_forward_chunk` directly.  Composes with
    `superstep` (K-aligned jumps) but not with `t0_mod` (see
    `check_chunk_config` for the remedy).
    """
    check_chunk_config(protocol, ms, t0_mod=t0_mod, superstep=superstep,
                       fast_forward=fast_forward)
    if fast_forward:
        base_ff = fast_forward_chunk(protocol, ms, superstep=superstep)

        def run_ff(net, pstate):
            net, pstate, _ = base_ff(net, pstate)
            return net, pstate

        return run_ff
    plan = None if t0_mod is None else phase_plan(protocol, t0_mod,
                                                  superstep)
    if plan is not None:
        lcm = plan.block
        if ms % lcm and not allow_unaligned:
            raise ValueError(
                f"phase-specialized chunk length {ms} is not a multiple of "
                f"the protocol schedule lcm {lcm}: reusing this chunk "
                "function would misalign the phase schedule after the "
                "first call. Use an lcm-multiple chunk, or pass "
                "allow_unaligned=True for a one-shot chunk and track "
                "t0_mod yourself.")
        hints = [h for step in plan.steps for h in step]
        blocks, tail = divmod(ms, lcm)

        def run_spec(net, pstate):
            def body(carry, _):
                net, ps = carry
                if superstep > 1:
                    for hints_k in plan.steps:
                        net, ps = step_kms(protocol, net, ps, superstep,
                                           hints_k=hints_k)
                else:
                    for h in hints:
                        net, ps = step_ms(protocol, net, ps, hints=h)
                return (net, ps), ()
            if blocks:
                (net, pstate), _ = jax.lax.scan(body, (net, pstate),
                                                length=blocks)
            for h in hints[:tail]:
                net, pstate = step_ms(protocol, net, pstate, hints=h)
            return net, pstate

        return run_spec

    if superstep > 1:
        def run_k(net, pstate):
            def body(carry, _):
                return step_kms(protocol, *carry, superstep), ()
            (net2, p2), _ = jax.lax.scan(body, (net, pstate),
                                         length=ms // superstep)
            return net2, p2

        return run_k

    def run(net, pstate):
        def body(carry, _):
            return step_ms(protocol, *carry), ()
        (net2, p2), _ = jax.lax.scan(body, (net, pstate), length=ms)
        return net2, p2

    return run


class Runner:
    """Drives a protocol; caches one jitted scan per distinct chunk length.

    The analogue of Network.runMs (Network.java:318-338) — but a whole chunk
    of milliseconds is a single device program.

    donate=True (the default) donates the whole state pytree to each
    chunk, so the chunk's outputs reuse its inputs' buffers; on a TPU v5e
    this is bit-identical to the undonated run at the headline Handel
    state (PR 21 chip run).

    `chunk_limit` ms, when set, splits longer requests into equal
    chunks plus one remainder (scan composition — bit-identical
    results): callers that must see the state at fixed chunk
    boundaries use it (the matrix driver's per-chunk twin).
    """

    def __init__(self, protocol, donate=True, chunk_limit=None,
                 superstep=1, fast_forward=False, metrics=None, trace=None,
                 audit=None):
        self.protocol = protocol
        self._jits = {}
        self._donate = donate
        self._validated = False
        self.chunk_limit = chunk_limit
        # fast_forward=True runs chunks through the quiet-window
        # while-loop engine (bit-identical) and accumulates the skip
        # stats (`ff_stats()` — utils/profiling.run_report reports
        # them).  Demoted silently when the protocol is ineligible,
        # matching the superstep demotion convention below.
        self._fast_forward = bool(fast_forward) and fast_forward_ok(protocol)
        # metrics (an obs.MetricsSpec) swaps in the instrumented chunk
        # builders: each chunk's MetricsCarry is appended to
        # `metrics_carries` (device arrays — no sync); `metrics_frame()`
        # fetches and stitches them.
        self._metrics = metrics
        # trace (an obs.TraceSpec) swaps in the flight-recorder chunk
        # builders (obs/trace.py — bit-identical trajectory); each
        # chunk's TraceCarry lands in `trace_carries` (device arrays —
        # no sync); `trace_frame()` decodes, `trace_stats()` surfaces
        # the truncation accounting (`run_report` prints it so a
        # clipped ring can never pass silently).
        if sum(p is not None for p in (metrics, trace, audit)) > 1:
            raise ValueError(
                "Runner supports ONE observability plane per pass "
                "(metrics=, trace=, audit=): the planes are separate "
                "carries and their builders do not compose yet. Fix: "
                "run the chunk twice (every plane is bit-identical on "
                "the trajectory), or pick the one you are debugging "
                "with")
        self._trace = trace
        # audit (an obs.AuditSpec) swaps in the invariant-monitor chunk
        # builders (obs/audit.py — bit-identical trajectory); each
        # chunk's AuditCarry lands in `audit_carries` (device arrays —
        # no sync); `audit_report()` decodes, and `run_report` prints a
        # LOUD verdict so a violated run can never pass silently.
        self._audit = audit
        self._ff_raw = []           # per-chunk device stats dicts
        self.metrics_carries = []
        self.trace_carries = []
        self.audit_carries = []
        # superstep=K fuses engine work across K-ms windows (step_kms,
        # bit-identical); the requested value is an UPPER BOUND — each
        # chunk runs the largest K <= it that `pick_superstep` proves
        # for the chunk length, entry time and config (a chunk that
        # proves nothing silently runs the per-ms path, results
        # identical).  "auto" lifts the bound to the engine default.
        if superstep == "auto":
            superstep = AUTO_SUPERSTEP_MAX
        self._superstep = int(superstep)

    def _chunk_fn(self, ms, superstep=1):
        key = (ms, superstep)
        if key not in self._jits:
            if self._metrics is not None and self._fast_forward:
                from ..obs.engine import fast_forward_chunk_metrics
                base = fast_forward_chunk_metrics(self.protocol, ms,
                                                  self._metrics,
                                                  superstep=superstep)
            elif self._metrics is not None:
                from ..obs.engine import scan_chunk_metrics
                base = scan_chunk_metrics(self.protocol, ms, self._metrics,
                                          superstep=superstep)
            elif self._trace is not None and self._fast_forward:
                from ..obs.trace import fast_forward_chunk_trace
                base = fast_forward_chunk_trace(self.protocol, ms,
                                                self._trace,
                                                superstep=superstep)
            elif self._trace is not None:
                from ..obs.trace import scan_chunk_trace
                base = scan_chunk_trace(self.protocol, ms, self._trace,
                                        superstep=superstep)
            elif self._audit is not None and self._fast_forward:
                from ..obs.audit import fast_forward_chunk_audit
                base = fast_forward_chunk_audit(self.protocol, ms,
                                                self._audit,
                                                superstep=superstep)
            elif self._audit is not None:
                from ..obs.audit import scan_chunk_audit
                base = scan_chunk_audit(self.protocol, ms, self._audit,
                                        superstep=superstep)
            elif self._fast_forward:
                base = fast_forward_chunk(self.protocol, ms,
                                          superstep=superstep)
            else:
                base = scan_chunk(self.protocol, ms, superstep=superstep)
            kw = {"donate_argnums": (0, 1)} if self._donate else {}
            self._jits[key] = jax.jit(base, **kw)
        return self._jits[key]

    def _call_chunk(self, fn, net, pstate):
        """Run one chunk and stash the fast-forward stats / metrics /
        trace carry its builder returns beyond ``(net, pstate)``."""
        out = fn(net, pstate)
        net, pstate = out[0], out[1]
        if self._fast_forward:
            self._ff_raw.append(out[2])
        if self._metrics is not None:
            self.metrics_carries.append(out[-1])
        if self._trace is not None:
            self.trace_carries.append(out[-1])
        if self._audit is not None:
            self.audit_carries.append(out[-1])
        return net, pstate

    def ff_stats(self):
        """Accumulated quiet-window skip accounting across every chunk
        this Runner ran, or None when fast-forward was off/never ran.
        Forces a device sync (host ints)."""
        if not self._ff_raw:
            return None
        import numpy as np
        return {
            "skipped_ms": sum(int(np.asarray(s["skipped_ms"]).reshape(-1)[0])
                              for s in self._ff_raw),
            "jump_count": sum(int(np.asarray(s["jump_count"]).reshape(-1)[0])
                              for s in self._ff_raw),
        }

    def metrics_frame(self):
        """Host-side `obs.MetricsFrame` stitched from every chunk's
        carry, or None when metrics were off/never ran."""
        if self._metrics is None or not self.metrics_carries:
            return None
        from ..obs.export import MetricsFrame
        return MetricsFrame.from_carries(self._metrics,
                                         self.metrics_carries)

    def trace_frame(self):
        """Host-side `obs.TraceFrame` stitched from every chunk's event
        ring, or None when tracing was off/never ran."""
        if self._trace is None or not self.trace_carries:
            return None
        from ..obs.decode import TraceFrame
        return TraceFrame.from_carries(self._trace, self.trace_carries)

    def trace_stats(self):
        """Flight-recorder truncation accounting across every chunk
        this Runner ran, or None when tracing was off/never ran: total
        recorded events, the per-chunk ring high-water mark, capacity,
        and the dropped-event count a silently clipped trace would
        otherwise hide.  Forces a device sync (host ints)."""
        if self._trace is None or not self.trace_carries:
            return None
        import numpy as np
        cursors = [np.asarray(jax.device_get(tc.cursor),
                              dtype=np.int64).reshape(-1)
                   for tc in self.trace_carries]
        dropped = sum(int(np.asarray(jax.device_get(tc.dropped),
                                     dtype=np.int64).sum())
                      for tc in self.trace_carries)
        return {"events": int(sum(c.sum() for c in cursors)),
                "high_water": int(max(c.max() for c in cursors)),
                "capacity": self._trace.capacity,
                "dropped": dropped}

    def audit_report(self):
        """Host-side `obs.AuditReport` stitched from every chunk's
        carry, or None when the audit plane was off/never ran.  Forces
        a device sync (host ints)."""
        if self._audit is None or not self.audit_carries:
            return None
        from ..obs.audit import monitored_invariants
        from ..obs.audit_report import AuditReport
        return AuditReport.from_carries(
            self._audit, self.audit_carries,
            monitored=monitored_invariants(self._audit,
                                           self.protocol.cfg))

    def audit_stats(self):
        """Audit verdict dict across every chunk this Runner ran, or
        None when the plane was off/never ran (`run_report` prints it
        LOUDLY — a violated run cannot pass silently)."""
        rep = self.audit_report()
        return None if rep is None else rep.stats()

    def run_report(self, net, wall_s=None):
        """One-line run summary (utils/profiling.run_report) carrying
        this Runner's quiet-window skip accounting, the trace
        truncation counters AND the audit verdict — a clipped event
        ring or a violated invariant shows up in bench output instead
        of passing silently."""
        from ..utils.profiling import run_report
        return run_report(net, wall_s, ff=self.ff_stats(),
                          trace=self.trace_stats(),
                          audit=self.audit_stats())

    def run_ms(self, net, pstate, ms: int):
        if not self._validated:
            validate = getattr(self.protocol.latency, "validate", None)
            if validate is not None and not isinstance(
                    jnp.asarray(net.nodes.city), jax.core.Tracer):
                validate(net.nodes)
            self._validated = True
        ms = int(ms)
        # Per-chunk superstep eligibility: K-aligned chunk + (statically
        # checkable) K-aligned entry time; a tracer entry time
        # conservatively falls back to the per-ms path.  The entry-time
        # readback blocks on the previous chunk, so it only happens when
        # superstep is actually enabled — the default path keeps fully
        # async dispatch.
        t_entry = None
        if self._superstep >= 2 and not isinstance(net.time,
                                                   jax.core.Tracer):
            t_entry = int(jax.device_get(net.time).reshape(-1)[0])
        stat_ms = (self._metrics.stat_each_ms
                   if self._metrics is not None else None)
        def eff(chunk_ms, t0):
            if self._superstep < 2:
                return 1
            return pick_superstep(self.protocol, chunk_ms, t0=t0,
                                  max_k=self._superstep,
                                  also_divides=stat_ms)
        if self.chunk_limit and ms > self.chunk_limit:
            # n_chunks equal pieces + one remainder piece at most: two
            # compiled programs for any length.
            whole, rem = divmod(ms, self.chunk_limit)
            fn = self._chunk_fn(self.chunk_limit,
                                eff(self.chunk_limit, t_entry))
            for _ in range(whole):
                net, pstate = self._call_chunk(fn, net, pstate)
                if t_entry is not None:
                    t_entry += self.chunk_limit
            if rem:
                net, pstate = self._call_chunk(
                    self._chunk_fn(rem, eff(rem, t_entry)), net, pstate)
            return net, pstate
        return self._call_chunk(self._chunk_fn(ms, eff(ms, t_entry)),
                                net, pstate)
