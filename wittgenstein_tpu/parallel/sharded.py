"""Node-axis sharded engine: shard_map over a device mesh with explicit
cross-shard message exchange.

SURVEY.md §5.7/§7.2.7: the reference's scaling dimension is node count and
in-flight messages (single JVM heap); the TPU analogue is sharding the
node-state struct-of-arrays and the mailbox across devices, with
cross-shard delivery riding ICI collectives.  This module implements that
design for *shard-local* protocols (each node's step reads only its own
state and inbox — PingPong-style workloads; the level-structured
aggregation protocols use the GSPMD path in __graft_entry__ instead, where
XLA partitions the global-gather ops and inserts the collectives).

Design:
* Every shard owns N/S nodes: their NodeState slice, a local mailbox ring
  (same layout as core.state, sized per shard), and a replicated broadcast
  table (a broadcast is O(1) state, so replication is free — the same
  reasoning that makes sendAll O(1) on one chip).
* A step: build the local inbox -> protocol.step on local nodes ->
  split the outbox by destination shard into fixed-capacity buckets ->
  `jax.lax.all_to_all` over the 'sp' mesh axis (one ICI exchange per ms)
  -> enqueue the received bucket into the local ring.
* Send capacity: each shard may send up to `xcap` messages per destination
  shard per ms; overflow is counted in `xdropped` (the sharded analogue of
  NetState.dropped — size it for the protocol).

Latency draws key on GLOBAL message indices and node ids, and the node
coordinate/city tables are replicated into every shard (three [N] int32
all_gathers per ms, riding the same ICI exchange), so a sharded run is
bit-identical to the single-chip run of the same protocol for EVERY
latency model, including the positional ones
(NetworkLatencyByDistanceWJitter / city models) — tested on the virtual
CPU mesh in tests/test_sharded.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import network as net_mod
from ..core.latency import full_latency
from ..core import builders
from ..core.latency import NetworkFixedLatency
from ..core.state import (EngineConfig, Inbox, NetState, Outbox,
                          empty_outbox, init_net)
from ..ops import prng


@struct.dataclass
class ShardedNet:
    """Per-shard simulator state; leading axis inside shard_map is local."""

    net: NetState              # node axis = local slice; bc_* replicated
    shard_id: jnp.ndarray      # int32 scalar — this shard's index
    xdropped: jnp.ndarray      # int32 scalar — cross-shard bucket overflow


def _shard_spec(mesh):
    return NamedSharding(mesh, P("sp"))


class ShardedRunner:
    """Runs a shard-local protocol over a mesh axis 'sp'.

    The protocol contract matches core.protocol, with one extra rule: its
    `step(pstate, nodes, inbox, t, key)` must only touch node-local state
    (no cross-node gathers) — outputs address any GLOBAL node id via the
    outbox, and the runner routes them.
    """

    def __init__(self, protocol, mesh: Mesh, xcap: int = None):
        if "sp" not in mesh.axis_names:
            raise ValueError("mesh must have an 'sp' axis")
        if protocol.cfg.spill_cap:
            # The sharded delivery path clamps far-future arrivals to the
            # ring edge (like spill_cap == 0); honoring the spill contract
            # here needs a sharded spill buffer — refuse rather than
            # silently diverge from the single-chip engine.
            raise NotImplementedError(
                "ShardedRunner does not support EngineConfig.spill_cap > 0;"
                " size `horizon` for the protocol instead")
        if protocol.cfg.box_split != 1:
            raise NotImplementedError(
                "ShardedRunner shards the ring by node range itself; use "
                "box_split == 1 (sub-plane splitting is a single-chip "
                "buffer-limit workaround)")
        self.protocol = protocol
        self.mesh = mesh
        self.n_shards = mesh.shape["sp"]
        cfg = protocol.cfg
        if cfg.n % self.n_shards:
            raise ValueError(f"node count {cfg.n} not divisible by "
                             f"{self.n_shards} shards")
        self.n_local = cfg.n // self.n_shards
        # local engine config: same ring geometry over the local node count
        self.lcfg = EngineConfig(
            n=self.n_local, horizon=cfg.horizon, inbox_cap=cfg.inbox_cap,
            payload_words=cfg.payload_words, out_deg=cfg.out_deg,
            bcast_slots=cfg.bcast_slots,
            msg_discard_time=cfg.msg_discard_time)
        # per-destination-shard exchange capacity per ms
        self.xcap = xcap or max(16, 2 * self.n_local * cfg.out_deg //
                                max(1, self.n_shards))

    # ---------------------------------------------------------------- init

    def init(self, seed):
        """Global init then shard: NodeState slices per shard, fresh local
        rings, replicated broadcast table."""
        from ..core.state import init_net
        cfg, S = self.protocol.cfg, self.n_shards
        net, pstate = self.protocol.init(seed)

        def split_nodes(x):
            return x.reshape((S, self.n_local) + x.shape[1:])

        nodes_sh = jax.tree.map(split_nodes, net.nodes)
        lnet = jax.vmap(
            lambda nd, sid: init_net(self.lcfg, nd, seed).replace(
                time=net.time))(nodes_sh, jnp.arange(S))
        # replicate the broadcast table
        def rep(x):
            return jnp.broadcast_to(x[None], (S,) + x.shape)
        lnet = lnet.replace(
            bc_active=rep(net.bc_active), bc_src=rep(net.bc_src),
            bc_time=rep(net.bc_time), bc_payload=rep(net.bc_payload),
            bc_size=rep(net.bc_size), bc_seed=rep(net.bc_seed),
            seed=jnp.full((S,), net.seed, jnp.int32),
            time=jnp.full((S,), 0, jnp.int32))
        snet = ShardedNet(net=lnet,
                          shard_id=jnp.arange(S, dtype=jnp.int32),
                          xdropped=jnp.zeros((S,), jnp.int32))
        ps_sh = jax.tree.map(
            lambda x: x.reshape((S, self.n_local) + x.shape[1:])
            if x.ndim >= 1 and x.shape[0] == cfg.n else
            jnp.broadcast_to(x[None], (S,) + x.shape), pstate)
        spec = _shard_spec(self.mesh)
        put = lambda x: jax.device_put(x, spec)
        return jax.tree.map(put, snet), jax.tree.map(put, ps_sh)

    # ---------------------------------------------------------------- step

    def _local_inbox(self, snet: ShardedNet, t, part_all=None,
                     extra_all=None, tables=None):
        """Local-ring slice + broadcast recompute for the local nodes.
        Returns ``(inbox, nodes, sizes)`` — `sizes` is the per-slot
        payload-byte view ``[nl, C + B]`` the trace plane records
        (delivery itself reads sizes only for the receive counters).

        Global semantics preserved: latency draws key on GLOBAL ids."""
        cfg, lcfg = self.protocol.cfg, self.lcfg
        model = self.protocol.latency
        net = snet.net
        nodes = net.nodes
        nl, c, b, f = lcfg.n, cfg.inbox_cap, cfg.bcast_slots, \
            cfg.payload_words
        h = t % cfg.horizon
        base = h * (nl * c)
        uc_data = jnp.stack(
            [jax.lax.dynamic_slice(net.box_data[fi], (base,),
                                   (nl * c,)).reshape(nl, c)
             for fi in range(f)], axis=-1)
        uc_src = jax.lax.dynamic_slice(net.box_src[0], (base,),
                                       (nl * c,)).reshape(nl, c)
        uc_size = jax.lax.dynamic_slice(net.box_size[0], (base,),
                                        (nl * c,)).reshape(nl, c)
        uc_valid = jnp.arange(c)[None, :] < net.box_count[h][:, None]
        uc_valid = uc_valid & (~nodes.down[:, None])
        if part_all is not None:
            # delivery-time partition check, like build_inbox: enqueue
            # already filtered cross-partition sends, so with STATIC
            # partitions this is a no-op — but a mid-run partition
            # (chaos plane) opening while a message is in flight must
            # drop it at delivery exactly as the single-chip engine
            # does (box_src carries global ids; empty slots are already
            # masked by the count check above)
            uc_valid = uc_valid & (part_all[uc_src] ==
                                   nodes.partition[:, None])

        # broadcast recompute over GLOBAL destination ids
        gids = snet.shard_id * nl + jnp.arange(nl, dtype=jnp.int32)
        delta = prng.uniform_delta(net.bc_seed[:, None], gids[None, :])
        lat = self._bc_latency(snet, net.bc_src[:, None], gids[None, :],
                               delta, extra_all, tables)
        not_disc = lat < cfg.msg_discard_time
        lat = jnp.clip(lat, 1, cfg.horizon - 2)
        arrival = net.bc_time[:, None] + 1 + lat
        bc_valid = (net.bc_active[:, None] & (arrival == t) & not_disc &
                    (~nodes.down[None, :]))
        if part_all is not None:
            bc_valid = bc_valid & (part_all[net.bc_src][:, None] ==
                                   nodes.partition[None, :])
        bc_valid = jnp.transpose(bc_valid)
        inbox = Inbox(
            data=jnp.concatenate(
                [uc_data, jnp.broadcast_to(net.bc_payload[None],
                                           (nl, b, f))], axis=1),
            src=jnp.concatenate(
                [uc_src, jnp.broadcast_to(net.bc_src[None], (nl, b))],
                axis=1),
            valid=jnp.concatenate([uc_valid, bc_valid], axis=1))
        recv = (jnp.sum(uc_valid, 1) + jnp.sum(bc_valid, 1)).astype(
            jnp.int32)
        rbytes = (jnp.sum(jnp.where(uc_valid, uc_size, 0), 1) +
                  jnp.sum(jnp.where(bc_valid,
                                    net.bc_size[None, :], 0), 1)
                  ).astype(jnp.int32)
        nodes = nodes.replace(
            msg_received=nodes.msg_received + recv,
            bytes_received=nodes.bytes_received + rbytes)
        sizes = jnp.concatenate(
            [uc_size, jnp.broadcast_to(net.bc_size[None, :], (nl, b))],
            axis=1)
        return inbox, nodes, sizes

    def _bc_latency(self, snet, src_g, dst_g, delta, extra_all=None,
                    tables=None):
        """Latency between global ids, any model: positional models read
        the replicated [N] coordinate/city tables (`tables`); per-node
        extra latency (tor) is honored via the replicated extra_all
        table."""
        model = self.protocol.latency

        class _NodesStub:
            extra_latency = jnp.zeros_like(delta)
            if tables is not None:
                x, y, city = tables

        lat = model.extended(_NodesStub(), src_g, dst_g, delta)
        if extra_all is not None:
            lat = lat + extra_all[src_g] + extra_all[dst_g]
        return jnp.maximum(1, lat) * (src_g != dst_g) + (src_g == dst_g)

    def step_fn(self, superstep: int = 1, trace_spec=None,
                audit_spec=None):
        """Returns the shard_map'ed step: one simulated ms (default), or
        one fused K-ms superstep window.

        ``trace_spec`` (an `obs.TraceSpec`) compiles the flight
        recorder into the step: the returned function then maps
        ``(snet, pstate, TraceCarry) -> (snet, pstate, TraceCarry)``
        with PER-SHARD event rings — deliveries recorded from each
        shard's local inbox (dst = global id) and sends from its
        outbox (src/aux = the same global ids/slot the latency draw
        keys on), per-ms exact inside a K window.  Scope note: the
        sharded recorder covers `send`/`deliver` (+ the node filter);
        drop/spill/bc_retire kinds are decided inside the exchange
        machinery and stay counter-only here (`xdropped`,
        `net.dropped`).  Tracing is a pure read of values the step
        already computes, so the (state, pstate) trajectory is
        bit-identical to the untraced step (tests/test_trace.py).

        The K generalization mirrors `core/network.step_kms`: the local
        ring rows are untouched inside the window (K <= the protocol's
        unicast latency floor + 1, gated by the caller through
        `check_chunk_config`), so the window runs K local inbox reads
        and protocol steps with per-ms-exact broadcast interleaving,
        then ONE K-row slot clear, ONE outbox split with per-ms ranks
        (cross-shard drop semantics stay exactly per-ms: each origin ms
        keeps its own xcap sub-bucket), ONE `all_to_all` ICI exchange —
        the sharded engine's per-ms fixed cost — and ONE sort+scatter
        bin of the received window (reordered origin-ms-major so same-
        (ms, dest) slot order matches the sequential path bit-for-bit).
        Messages carry their origin-ms offset through the exchange so
        the receiver keys each latency draw on the origin ms, exactly
        as the per-ms path does.

        ``audit_spec`` (an `obs.AuditSpec`) compiles the invariant
        audit plane into the step instead: the returned function then
        carries a per-shard `AuditCarry` third argument.  The sharded
        monitors cover the local-ring/monotonicity invariants per
        shard, the replicated broadcast table's consistency, local
        ring conservation (received exchange candidates vs Δ local
        occupancy), and CROSS-SHARD exchange conservation: the
        per-destination-shard bucket counts each shard placed ride one
        extra tiny ``[S]`` all_to_all, so every shard verifies that
        what its peers claim they sent it equals what actually arrived
        (obs/audit.py `fold_window_sharded`).  Pure reads of values
        the step already computes — bit-identical trajectory
        (tests/test_audit.py)."""
        cfg, lcfg, S = self.protocol.cfg, self.lcfg, self.n_shards
        nl, k, xcap = self.n_local, cfg.out_deg, self.xcap
        K = superstep
        proto = self.protocol
        fw = cfg.payload_words
        if trace_spec is not None and audit_spec is not None:
            raise ValueError("one observability plane per step_fn")
        if trace_spec is not None:
            from ..obs.trace import KIND, _append
        if audit_spec is not None:
            from ..obs.audit import fold_window_sharded

        def one_shard(snet: ShardedNet, pstate, tc=None):
            net = snet.net
            t = net.time
            gids0 = snet.shard_id * nl + jnp.arange(nl, dtype=jnp.int32)
            # Chaos-plane hook (see network.step_kms): the window-entry
            # fault application runs on the LOCAL node slice (gids map
            # local rows to the schedule's global ids) BEFORE the
            # replicated-table gathers below, so every shard's view of
            # down/partition state is the post-fault one.
            af = getattr(proto, "apply_faults", None)
            if af is not None:
                net = af(net, t, gids=gids0)
            # replicated per-node tables for cross-shard checks (one [N]
            # all_gather each; rides the same ICI exchange)
            part_all = jax.lax.all_gather(net.nodes.partition,
                                          "sp").reshape(-1)
            extra_all = jax.lax.all_gather(net.nodes.extra_latency,
                                           "sp").reshape(-1)
            down_all = jax.lax.all_gather(net.nodes.down, "sp").reshape(-1)
            # Positional latency models read global coordinates/cities;
            # distance-free models declare `positional = False` and skip
            # the three [N] gathers (default True: unknown custom models
            # get the tables).
            if getattr(proto.latency, "positional", True):
                tables = (
                    jax.lax.all_gather(net.nodes.x, "sp").reshape(-1),
                    jax.lax.all_gather(net.nodes.y, "sp").reshape(-1),
                    jax.lax.all_gather(net.nodes.city, "sp").reshape(-1))
            else:
                tables = None
            snet = snet.replace(net=net)
            step = getattr(proto, "step_sharded", None)
            aobs = None
            if audit_spec is not None:
                # window-entry observations for the sharded fold: the K
                # consumed rows are intact until the deferred clear, so
                # one contiguous slice reads them all up-front
                aobs = {
                    "t_entry": jnp.asarray(t, jnp.int32),
                    "occ_entry": jnp.sum(net.box_count).astype(jnp.int32),
                    "dropped_entry": net.dropped,
                    "consumed": jnp.sum(jax.lax.dynamic_slice(
                        net.box_count, (t % cfg.horizon, 0),
                        (K, nl))).astype(jnp.int32),
                    "candidates": jnp.asarray(0, jnp.int32),
                    "xmismatch": jnp.asarray(0, jnp.int32),
                }

            # ---- K protocol steps: per-ms local inbox reads (the local
            # ring is untouched inside the window — binning is deferred)
            # with per-ms-exact broadcast retire/deliver/enqueue ----
            parts = []          # per-ms flattened outbox batches
            for i in range(K):
                ti = t + i
                net = net.replace(bc_active=net.bc_active & (
                    (ti - net.bc_time) < cfg.horizon))
                inbox, nodes, in_sizes = self._local_inbox(
                    snet.replace(net=net), ti, part_all, extra_all,
                    tables)
                if trace_spec is not None and trace_spec.enabled("deliver"):
                    width = inbox.valid.shape[1]
                    dst_g = jnp.broadcast_to(gids0[:, None], (nl, width))
                    slot = jnp.broadcast_to(
                        jnp.arange(width, dtype=jnp.int32)[None, :],
                        (nl, width))
                    tc = _append(trace_spec, tc, ti, KIND["deliver"],
                                 inbox.src.reshape(-1),
                                 dst_g.reshape(-1),
                                 in_sizes.reshape(-1), slot.reshape(-1),
                                 inbox.valid.reshape(-1))
                key = jax.random.fold_in(jax.random.PRNGKey(net.seed), ti)
                if step is not None:
                    # Shard-aware protocols receive their GLOBAL node ids.
                    pstate, nodes, out = step(pstate, nodes, inbox, ti,
                                              key, gids0)
                else:
                    pstate, nodes, out = proto.step(pstate, nodes, inbox,
                                                    ti, key)
                # Width may be narrower than cfg.out_deg (Outbox.slot0):
                # the latency key below stays on the full-width slot id.
                ke = out.dest.shape[1]
                m = nl * ke
                dest_i = out.dest.reshape(m)
                size_i = out.size.reshape(m)
                want_i = (dest_i >= 0) & (~nodes.down[jnp.arange(m) // ke])
                # counters for attempted sends (parity w/ enqueue_unicast)
                sent = nodes.msg_sent.at[jnp.arange(m) // ke].add(
                    want_i.astype(jnp.int32))
                sbytes = nodes.bytes_sent.at[jnp.arange(m) // ke].add(
                    jnp.where(want_i, size_i, 0))
                nodes = nodes.replace(msg_sent=sent, bytes_sent=sbytes)
                net = net.replace(nodes=nodes)
                if trace_spec is not None and trace_spec.enabled("send"):
                    tc = _append(
                        trace_spec, tc, ti, KIND["send"],
                        jnp.repeat(gids0, ke),
                        jnp.clip(dest_i, 0, cfg.n - 1), size_i,
                        jnp.repeat(gids0, ke) * k + out.slot0 +
                        jnp.arange(m, dtype=jnp.int32) % ke, want_i)
                parts.append((
                    jnp.repeat(gids0, ke),              # global src ids
                    dest_i,
                    out.payload.reshape(m, fw),
                    size_i,
                    out.delay.reshape(m),
                    # Global stable message index (src_g * out_deg + slot
                    # id): the single-chip engine keys its latency delta
                    # on exactly this (enqueue_unicast), so carrying it
                    # through the exchange keeps jittered models
                    # bit-identical to the unsharded run.
                    jnp.repeat(gids0, ke) * k + out.slot0 +
                    jnp.arange(m, dtype=jnp.int32) % ke,
                    jnp.full((m,), i, jnp.int32),       # origin-ms offset
                    want_i,
                ))
                # ---- broadcasts: replicated table, all shards agree ----
                req = out.bcast & (~nodes.down)
                if trace_spec is not None and trace_spec.enabled("send"):
                    tc = _append(trace_spec, tc, ti, KIND["send"], gids0,
                                 jnp.full((nl,), -1, jnp.int32),
                                 out.bcast_size,
                                 jnp.full((nl,), -1, jnp.int32), req)
                # gather every shard's requests (replicated result)
                req_all = jax.lax.all_gather(req, "sp").reshape(-1)
                pl_all = jax.lax.all_gather(out.bcast_payload,
                                            "sp").reshape(cfg.n, fw)
                sz_all = jax.lax.all_gather(out.bcast_size,
                                            "sp").reshape(-1)
                gout = empty_outbox(cfg).replace(
                    bcast=req_all, bcast_payload=pl_all, bcast_size=sz_all)
                # reuse the single-chip broadcast allocator on a stub net
                # (bc_* fields are global); counters from it are per-
                # GLOBAL-node, so apply the local slice separately
                gnet2 = net_mod.enqueue_broadcast(
                    EngineConfig(n=cfg.n, horizon=cfg.horizon,
                                 inbox_cap=cfg.inbox_cap,
                                 payload_words=fw, out_deg=cfg.out_deg,
                                 bcast_slots=cfg.bcast_slots),
                    net.replace(nodes=jax.tree.map(
                        lambda x: jnp.zeros((cfg.n,) + x.shape[1:],
                                            x.dtype),
                        net.nodes)), gout, ti)
                bsent = net.nodes.msg_sent + jnp.where(
                    req, cfg.n, 0).astype(jnp.int32)
                bbytes = net.nodes.bytes_sent + jnp.where(
                    req, out.bcast_size * cfg.n, 0)
                net = net.replace(
                    nodes=net.nodes.replace(msg_sent=bsent,
                                            bytes_sent=bbytes),
                    bc_active=gnet2.bc_active, bc_src=gnet2.bc_src,
                    bc_time=gnet2.bc_time, bc_payload=gnet2.bc_payload,
                    bc_size=gnet2.bc_size, bc_seed=gnet2.bc_seed,
                    bc_dropped=gnet2.bc_dropped)

            # ---- ONE K-row slot clear (entry time ≡ 0 mod K: no wrap) --
            net = net.replace(box_count=jax.lax.dynamic_update_slice(
                net.box_count, jnp.zeros((K, nl), jnp.int32),
                (t % cfg.horizon, 0)))

            # ---- split the window's outboxes by destination shard ----
            # Rank per (dest-shard, ORIGIN MS) group: each origin ms
            # keeps its own xcap sub-bucket, so cross-shard drop
            # semantics stay exactly per-ms whatever K is.
            src_g = jnp.concatenate([p[0] for p in parts])
            dest = jnp.concatenate([p[1] for p in parts])
            payload = jnp.concatenate([p[2] for p in parts])
            size = jnp.concatenate([p[3] for p in parts])
            delay = jnp.concatenate([p[4] for p in parts])
            midx = jnp.concatenate([p[5] for p in parts])
            toff = jnp.concatenate([p[6] for p in parts])
            want = jnp.concatenate([p[7] for p in parts])
            ma = src_g.shape[0]
            dshard = jnp.clip(dest, 0, cfg.n - 1) // nl
            order = jnp.argsort(jnp.where(want, dshard, S), stable=True)
            ds_s = jnp.where(want, dshard, S)[order]
            to_s = toff[order]
            idx = jnp.arange(ma, dtype=jnp.int32)
            new_grp = ((ds_s != jnp.roll(ds_s, 1)) |
                       (to_s != jnp.roll(to_s, 1))).at[0].set(True)
            rank = idx - jax.lax.cummax(jnp.where(new_grp, idx, 0))
            ok_s = (ds_s < S) & (rank < xcap)
            slot = jnp.where(ok_s, (ds_s * K + to_s) * xcap + rank,
                             S * K * xcap)
            # bucket fields [S * K * xcap, ...]
            def scatter(vals, fill):
                buf = jnp.full((S * K * xcap,) + vals.shape[1:], fill,
                               vals.dtype)
                return buf.at[slot].set(vals[order], mode="drop")
            b_src = scatter(src_g, -1)
            b_dest = scatter(dest, -1)
            b_payload = scatter(payload, 0)
            b_size = scatter(size, 0)
            b_delay = scatter(delay, 0)
            b_midx = scatter(midx, 0)
            b_toff = scatter(toff, 0)
            xdrop = jnp.sum((ds_s < S) & ~ok_s).astype(jnp.int32)

            # ---- the ICI exchange: ONE all_to_all for the window ----
            def xc(x):
                return jax.lax.all_to_all(
                    x.reshape((S, K * xcap) + x.shape[1:])[None],
                    "sp", split_axis=1, concat_axis=1)[0].reshape(
                    (S * K * xcap,) + x.shape[1:])

            # Origin-ms-major reorder of the received window: the per-ms
            # path bins ms i's messages before ms i+1's whatever their
            # source shard, and the stable binning sort below preserves
            # input order within a (rel, dest) group — so the input must
            # be (ms, shard, rank)-ordered for bit-identical slots.
            def omm(x):
                return x.reshape((S, K, xcap) + x.shape[1:]).swapaxes(
                    0, 1).reshape((S * K * xcap,) + x.shape[1:])

            xb_dest = xc(b_dest)
            if aobs is not None and audit_spec.enabled(
                    "shard_conservation"):
                # cross-shard conservation: what each peer CLAIMS it
                # sent me (its per-dest-shard bucket counts, exchanged
                # over one tiny [S] all_to_all) must equal what
                # actually arrived in its segment of the exchange
                sent_to = jnp.sum(
                    ok_s[:, None] &
                    (ds_s[:, None] == jnp.arange(S, dtype=jnp.int32)[
                        None, :]), axis=0).astype(jnp.int32)
                claims = jax.lax.all_to_all(
                    sent_to.reshape(S, 1)[None], "sp", split_axis=1,
                    concat_axis=1)[0].reshape(S)
                received_from = jnp.sum(
                    xb_dest.reshape(S, K * xcap) >= 0,
                    axis=1).astype(jnp.int32)
                aobs["xmismatch"] = jnp.sum(
                    jnp.abs(claims - received_from)).astype(jnp.int32)
            r_src = omm(xc(b_src))
            r_dest = omm(xb_dest)
            r_payload = omm(xc(b_payload))
            r_size = omm(xc(b_size))
            r_delay = omm(xc(b_delay))
            r_midx = omm(xc(b_midx))
            r_toff = omm(xc(b_toff))

            # ---- enqueue received into the local ring ----
            dl = jnp.clip(r_dest - snet.shard_id * nl, 0, nl - 1)
            # latency keyed by the global flat message index AND the
            # message's origin ms — the same draw enqueue_unicast makes
            # on one chip at that ms
            seed_t = prng.hash3(net.seed, prng.TAG_LATENCY, t + r_toff)
            delta = prng.uniform_delta(seed_t, r_midx)
            lat = self._bc_latency(snet, jnp.maximum(r_src, 0),
                                   jnp.where(r_dest >= 0, r_dest, 0),
                                   delta, extra_all, tables)
            # the same validity gates as enqueue_unicast: discard window,
            # destination down, cross-partition drop
            ok = (r_dest >= 0) & (lat < cfg.msg_discard_time) & \
                ~net.nodes.down[dl] & \
                (part_all[jnp.maximum(r_src, 0)] ==
                 net.nodes.partition[dl])
            if aobs is not None:
                aobs["candidates"] = jnp.sum(ok).astype(jnp.int32)
            raw_total = jnp.clip(r_delay, 0, None) + jnp.maximum(lat, 1)
            total = jnp.clip(raw_total, 1, cfg.horizon - 2)
            # Arrivals past the ring clamp (counted, like the single-chip
            # engine with spill_cap == 0; spill is unsupported here — see
            # __init__).
            n_clamped = jnp.sum(ok & (raw_total != total)).astype(jnp.int32)
            net = net.replace(clamped=net.clamped + n_clamped)
            arrival = t + r_toff + 1 + total
            from ..ops.pallas_route import route_enabled
            if route_enabled():
                # Fused Pallas binning of the received window — same
                # cells, same slot order (the local-ring half of the
                # WTPU_PALLAS_ROUTE megakernel; the origin-ms-major
                # reorder above already put the input in the per-ms
                # path's stable order).
                from ..ops.pallas_route import bin_into_ring_planes
                box_data, box_src, box_size, box_count, n_drop = \
                    bin_into_ring_planes(
                        net.box_data, net.box_src, net.box_size,
                        net.box_count, arrival % cfg.horizon, dl,
                        r_src, r_size, r_payload, ok,
                        horizon=cfg.horizon, cap=cfg.inbox_cap, n=nl,
                        split=1, payload_words=fw)
                dropped = net.dropped + n_drop
            else:
                mx = S * K * xcap
                big = jnp.int32(0x7FFFFFFF)
                rel_k = jnp.where(ok, arrival - t, big)
                d_k = jnp.where(ok, dl, big)
                o1 = jnp.argsort(d_k, stable=True)
                order2 = o1[jnp.argsort(rel_k[o1], stable=True)]
                rel_s, d_s = rel_k[order2], d_k[order2]
                idx2 = jnp.arange(mx, dtype=jnp.int32)
                ng = ((rel_s != jnp.roll(rel_s, 1)) |
                      (d_s != jnp.roll(d_s, 1))).at[0].set(True)
                rank2 = idx2 - jax.lax.cummax(jnp.where(ng, idx2, 0))
                h_s = ((t + rel_s) % cfg.horizon)
                ok2 = (rel_s < big) & (rank2 + net.box_count[
                    jnp.clip(h_s, 0, cfg.horizon - 1),
                    jnp.clip(d_s, 0, nl - 1)] < cfg.inbox_cap)
                slot2 = net.box_count[jnp.clip(h_s, 0, cfg.horizon - 1),
                                      jnp.clip(d_s, 0, nl - 1)] + rank2
                hnc = cfg.horizon * nl * cfg.inbox_cap
                flat = (jnp.clip(h_s, 0, cfg.horizon - 1) * nl +
                        jnp.clip(d_s, 0, nl - 1)) * cfg.inbox_cap + \
                    jnp.where(ok2, slot2, 0)
                flat_w = jnp.where(ok2, flat, hnc)
                pl_s = r_payload[order2]
                box_data = tuple(
                    net.box_data[fi].at[flat_w].set(
                        pl_s[:, fi], mode="drop", unique_indices=True)
                    for fi in range(fw))
                box_src = (net.box_src[0].at[flat_w].set(
                    r_src[order2], mode="drop", unique_indices=True),)
                box_size = (net.box_size[0].at[flat_w].set(
                    r_size[order2], mode="drop", unique_indices=True),)
                box_count = net.box_count.at[
                    jnp.clip(h_s, 0, cfg.horizon - 1),
                    jnp.clip(d_s, 0, nl - 1)].add(ok2.astype(jnp.int32),
                                                  mode="drop")
                dropped = net.dropped + jnp.sum(
                    (rel_s < big) & ~ok2).astype(jnp.int32)

            net = net.replace(
                box_data=box_data, box_src=box_src, box_size=box_size,
                box_count=box_count, dropped=dropped, time=t + K)
            snet = snet.replace(net=net, xdropped=snet.xdropped + xdrop)
            if aobs is not None:
                tc = fold_window_sharded(audit_spec, cfg, tc, aobs,
                                         snet, K)
            if tc is not None:
                return snet, pstate, tc
            return snet, pstate

        traced = trace_spec is not None or audit_spec is not None

        def wrapped(snet, pstate, tc=None):
            # shard_map blocks keep a leading length-1 shard axis; peel it
            # off for the body and restore it for the output specs.
            sq = lambda x: x.reshape(x.shape[1:])
            un = lambda x: x.reshape((1,) + x.shape)
            if traced:
                sn2, ps2, tc2 = one_shard(jax.tree.map(sq, snet),
                                          jax.tree.map(sq, pstate),
                                          jax.tree.map(sq, tc))
                return (jax.tree.map(un, sn2), jax.tree.map(un, ps2),
                        jax.tree.map(un, tc2))
            sn2, ps2 = one_shard(jax.tree.map(sq, snet),
                                 jax.tree.map(sq, pstate))
            return jax.tree.map(un, sn2), jax.tree.map(un, ps2)

        spec = P("sp")
        specs = (spec,) * (3 if traced else 2)
        # check_vma off: the per-shard body mixes replicated broadcast
        # state with sharded node state.
        return jax.shard_map(wrapped, mesh=self.mesh, in_specs=specs,
                             out_specs=specs, check_vma=False)

    def _metric_values(self, spec, snet):
        """Global-aggregate counter values from the sharded state —
        the sharded analogue of obs.plane.counter_values.  Reductions
        over the shard axis lower to in-mesh collectives; everything
        stays on device (no host sync)."""
        net = snet.net
        nodes = net.nodes
        cols = set(spec.columns)
        out = {}
        if "msg_sent" in cols:
            out["msg_sent"] = jnp.sum(nodes.msg_sent)
        if "msg_received" in cols:
            out["msg_received"] = jnp.sum(nodes.msg_received)
        if "bytes_sent" in cols:
            out["bytes_sent"] = jnp.sum(nodes.bytes_sent)
        if "bytes_received" in cols:
            out["bytes_received"] = jnp.sum(nodes.bytes_received)
        if "done_count" in cols:
            out["done_count"] = jnp.sum((~nodes.down) & (nodes.done_at > 0))
        if "live_count" in cols:
            out["live_count"] = jnp.sum(~nodes.down)
        if "ring_rows" in cols:
            # box_count is [S, H, n_local]: a ring ROW is global (one
            # per ms slot), occupied when any shard holds a delivery.
            out["ring_rows"] = jnp.sum(
                jnp.any(net.box_count > 0, axis=(0, 2)))
        if "ring_occupancy" in cols:
            out["ring_occupancy"] = jnp.sum(net.box_count)
        if "bc_live" in cols:
            # bc table is replicated per shard; count one shard's view.
            out["bc_live"] = jnp.sum(net.bc_active[0])
        if "spill_hwm" in cols:
            out["spill_hwm"] = jnp.asarray(0, jnp.int32)  # spill unsupported
        if "drop_count" in cols:
            # dropped/clamped/xdropped are per-shard (local ring + local
            # exchange) — sum; bc_dropped rides the REPLICATED broadcast
            # table (every shard computes the same global value, like
            # bc_active above) — one shard's view, not a sum.
            out["drop_count"] = (
                jnp.sum(net.dropped) + net.bc_dropped[0] +
                jnp.sum(net.clamped) + jnp.sum(snet.xdropped))
        return {k: v.astype(jnp.int32) for k, v in out.items()}

    def run_ms(self, snet, pstate, ms: int, metrics=None,
               superstep: int = 1, trace=None, audit=None):
        """Advance `ms` milliseconds.  ``metrics`` (an
        `obs.MetricsSpec`) additionally records the global-aggregate
        interval series on device and returns ``(snet, pstate,
        MetricsCarry)`` — the sharded twin of
        `obs.engine.scan_chunk_metrics`.

        ``trace`` (an `obs.TraceSpec`) compiles the flight recorder
        into the step instead (`step_fn(trace_spec=...)` — per-shard
        event rings, deliver/send kinds) and returns ``(snet, pstate,
        TraceCarry)`` with a leading shard axis on the carry;
        `obs.TraceFrame.from_carry` merges the shards onto one
        timeline.  One plane per pass (both are bit-identical on the
        trajectory — run twice to get both).

        ``audit`` (an `obs.AuditSpec`) compiles the invariant audit
        plane into the step (`step_fn(audit_spec=...)` — local + cross-
        shard conservation monitors) and returns ``(snet, pstate,
        AuditCarry)`` with a leading shard axis on the carry;
        `obs.AuditReport.from_carry` merges the shards onto one
        verdict.  One plane per pass, like metrics/trace.

        ``superstep=K`` advances in fused K-ms windows (one ICI
        exchange, one sort/scatter bin and one slot clear per window —
        `step_fn(superstep=K)`, bit-identical); gated by the shared
        K-aware eligibility check plus an entry-time alignment read
        (blocks on in-flight work only when a superstep is requested)."""
        from ..core.network import check_chunk_config

        ms = int(ms)
        if sum(p is not None for p in (metrics, trace, audit)) > 1:
            raise ValueError(
                "run_ms(metrics=, trace=, audit=) is one plane per "
                "pass: run the chunk twice (every plane is "
                "bit-identical on the trajectory)")
        check_chunk_config(self.protocol, ms, superstep=superstep)
        if superstep > 1:
            if metrics is not None and metrics.stat_each_ms % superstep:
                raise ValueError(
                    f"superstep={superstep} windows record at window "
                    f"boundaries: stat_each_ms ({metrics.stat_each_ms}) "
                    "must be a multiple of the superstep")
            t_entry = int(np.asarray(
                jax.device_get(snet.net.time)).reshape(-1)[0])
            if t_entry % superstep:
                raise ValueError(
                    f"superstep={superstep} needs a K-aligned entry time "
                    f"(run is at t={t_entry}). Fix: advance "
                    f"{superstep - t_entry % superstep} ms with "
                    "superstep=1 first, or keep chunk lengths multiples "
                    "of the superstep from t=0")
        if not hasattr(self, "_jits"):
            self._jits = {}
            self._steps = {}
        if (superstep, trace, audit) not in self._steps:
            self._steps[(superstep, trace, audit)] = self.step_fn(
                superstep=superstep, trace_spec=trace, audit_spec=audit)
        key = (ms, metrics, trace, audit, superstep)
        if key not in self._jits:
            step = self._steps[(superstep, trace, audit)]
            if trace is not None:
                from ..obs.trace import init_trace

                @jax.jit
                def run(sn, ps):
                    tc0 = jax.vmap(lambda _: init_trace(trace))(
                        sn.net.time)

                    def body(carry, _):
                        return step(*carry), ()
                    (sn2, ps2, tc), _ = jax.lax.scan(
                        body, (sn, ps, tc0), length=ms // superstep)
                    return sn2, ps2, tc
            elif audit is not None:
                from ..obs.audit import init_audit_sharded

                @jax.jit
                def run(sn, ps):
                    ac0 = jax.vmap(
                        lambda s: init_audit_sharded(audit, s))(sn)

                    def body(carry, _):
                        return step(*carry), ()
                    (sn2, ps2, ac), _ = jax.lax.scan(
                        body, (sn, ps, ac0), length=ms // superstep)
                    return sn2, ps2, ac
            elif metrics is None:
                @jax.jit
                def run(sn, ps):
                    def body(carry, _):
                        return step(*carry), ()
                    (sn2, ps2), _ = jax.lax.scan(body, (sn, ps),
                                                 length=ms // superstep)
                    return sn2, ps2
            else:
                from ..obs.plane import init_metrics, record

                @jax.jit
                def run(sn, ps):
                    mc0 = init_metrics(metrics, ms, sn.net.time[0])

                    def body(carry, _):
                        sn, ps, mc = carry
                        sn, ps = step(sn, ps)
                        mc = record(metrics, mc, sn.net.time[0] - 1,
                                    self._metric_values(metrics, sn),
                                    n_steps=superstep)
                        return (sn, ps, mc), ()
                    (sn2, ps2, mc), _ = jax.lax.scan(body, (sn, ps, mc0),
                                                     length=ms // superstep)
                    return sn2, ps2, mc

            self._jits[key] = run
        with self.mesh:
            return self._jits[key](snet, pstate)

    # ---------------------------------------------------------------- util

    def gather_nodes(self, snet):
        """Collect the sharded NodeState back to a global one (host)."""
        return jax.tree.map(
            lambda x: np.asarray(x).reshape((-1,) + x.shape[2:]),
            snet.net.nodes)


# --------------------------------------------------------------- demo


@struct.dataclass
class RingState:
    received: jnp.ndarray   # int32 [N] — payload sum received
    count: jnp.ndarray      # int32 [N]


class RingForward:
    """Shard-local protocol: every node sends its id to (id + stride) % N
    each ms; nodes accumulate what they receive.  Exercises cross-shard
    unicast routing + the broadcast path (node 0 broadcasts at t == 0)."""

    # dest = (id + stride) % N with stride % N != 0 in every in-tree
    # config — never self (core/network.unicast_floor_ms).
    may_self_send = False

    def __init__(self, n=64, stride=9, latency=10, horizon=64):
        self.node_count = n
        self.stride = stride
        self.latency = (NetworkFixedLatency(latency)
                        if isinstance(latency, int) else latency)
        self.cfg = EngineConfig(n=n, horizon=horizon, inbox_cap=8,
                                payload_words=1, out_deg=1, bcast_slots=2)

    def init(self, seed):
        nodes = builders.NodeBuilder().build(seed, self.cfg.n)
        net = init_net(self.cfg, nodes, seed)
        return net, RingState(
            received=jnp.zeros((self.cfg.n,), jnp.int32),
            count=jnp.zeros((self.cfg.n,), jnp.int32))

    def _step(self, pstate, nodes, inbox, t, key, gids):
        got = jnp.sum(jnp.where(inbox.valid, inbox.data[:, :, 0], 0),
                      axis=1).astype(jnp.int32)
        cnt = jnp.sum(inbox.valid, axis=1).astype(jnp.int32)
        pstate = pstate.replace(received=pstate.received + got,
                                count=pstate.count + cnt)
        # Outbox sized to THIS slice (local under the sharded runner).
        nloc = gids.shape[0]
        send = t < 5                      # five rounds of sends
        dest = jnp.where(send, (gids + self.stride) % self.node_count, -1)
        out = Outbox(
            dest=dest[:, None],
            payload=(gids * 10)[:, None, None].astype(jnp.int32),
            size=jnp.ones((nloc, 1), jnp.int32),
            delay=jnp.zeros((nloc, 1), jnp.int32),
            bcast=(gids == 0) & (t == 0),
            bcast_payload=jnp.full((nloc, 1), 777, jnp.int32),
            bcast_size=jnp.ones((nloc,), jnp.int32))
        return pstate, nodes, out

    def step(self, pstate, nodes, inbox, t, key):
        gids = jnp.arange(self.cfg.n, dtype=jnp.int32)
        return self._step(pstate, nodes, inbox, t, key, gids)

    def step_sharded(self, pstate, nodes, inbox, t, key, gids):
        return self._step(pstate, nodes, inbox, t, key, gids)
