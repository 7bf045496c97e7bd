"""Batch-folded engine: the seed axis folded into the mailbox scatter.

Why this exists (reports/PROFILE_r4.md): under `jax.vmap`, the mailbox
ring scatter lowers to a SEQUENTIAL loop over the seed batch — XLA
materializes each seed's updated plane and copies it back with a
whole-plane dynamic-update-slice (80 x 25 MB per fused superstep at the
2048n x 16 headline config = 5.2 s per 200-ms chunk, 13% of device
time).  Folding the seed index into the flat scatter index turns those
8000 serialized plane copies into ONE scatter per plane.

Scope: the high-throughput bench path — protocols with
``spill_cap == 0`` and ``bcast_slots == 0`` (Handel exact + cardinal,
GSF).  Everything except the mailbox machinery stays the SAME code,
vmapped (protocol steps, routing, latency draws — their lowering was
already efficient).  All runs advance in lockstep (same `t`), which the
bench/harness batch paths guarantee.

Bit-equality with `jax.vmap(scan_chunk(...))` is asserted in
tests/test_batched.py: the folded scatter writes the same cells in the
same deterministic order (the per-seed sort keys and ranks are
unchanged; seeds never collide since the fold offsets by seed stride).

Observability: the flight-recorder twin of `scan_chunk_batched` is
`obs.trace.scan_chunk_batched_trace` — it runs the VMAPPED window
engine with per-ms taps (the folded scatter is a layout optimization;
the bit-equality above is exactly what makes the vmapped traced
trajectory the one this engine computes), so there is no tap parameter
on `step_kms_batched` itself.  The metrics twin
(`obs.engine.scan_chunk_batched_metrics`) does wrap the folded engine
directly — it only reads state between windows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .network import (STAGE_BIN, STAGE_INBOX, STAGE_PROTOCOL, STAGE_ROUTE,
                      _route_unicast, check_chunk_config, fast_forward_ok,
                      superstep_ok)
from .protocol import FAR_FUTURE
from .state import EngineConfig, Inbox, NetState


def _batched_bin(cfg: EngineConfig, net: NetState, t, src, dest, arrival,
                 payload, size, valid):
    """[R, m]-batched ring binning with the seed axis folded into the
    flat scatter index.  Mirrors network._bin_into_ring exactly per seed
    (same keys, same stable order, same slot assignment).

    ``WTPU_PALLAS_ROUTE=1`` swaps the folded sort/scatter for the fused
    Pallas routing megakernel (ops/pallas_route.py, seed axis as a grid
    dimension — bit-identical, tests/test_pallas_route.py)."""
    from ..ops.pallas_route import route_enabled
    if route_enabled():
        from ..ops.pallas_route import bin_into_ring_planes
        box_data, box_src, box_size, box_count, n_dropped = \
            bin_into_ring_planes(
                net.box_data, net.box_src, net.box_size, net.box_count,
                arrival % cfg.horizon, dest, src, size, payload, valid,
                horizon=cfg.horizon, cap=cfg.inbox_cap, n=cfg.n,
                split=cfg.box_split, payload_words=cfg.payload_words,
                seed_axis=True)
        return net.replace(box_data=box_data, box_src=box_src,
                           box_size=box_size, box_count=box_count), \
            n_dropped
    n, c = cfg.n, cfg.inbox_cap
    p, ns = cfg.box_split, cfg.split_n
    r, m = src.shape
    rel = arrival - t
    big = jnp.int32(0x7FFFFFFF)
    rel_k = jnp.where(valid, rel, big)
    dest_k = jnp.where(valid, dest, big)
    o1 = jnp.argsort(dest_k, axis=1, stable=True)
    order = jnp.take_along_axis(
        o1, jnp.argsort(jnp.take_along_axis(rel_k, o1, axis=1), axis=1,
                        stable=True), axis=1)
    rel_s = jnp.take_along_axis(rel_k, order, axis=1)
    dest_s = jnp.take_along_axis(dest_k, order, axis=1)
    idx = jnp.arange(m, dtype=jnp.int32)[None, :]
    new_grp = ((rel_s != jnp.roll(rel_s, 1, axis=1)) |
               (dest_s != jnp.roll(dest_s, 1, axis=1)))
    new_grp = new_grp.at[:, 0].set(True)
    rank = idx - jax.lax.cummax(jnp.where(new_grp, idx, 0), axis=1)

    h_s = jnp.take_along_axis(arrival % cfg.horizon, order, axis=1)
    d_s = jnp.take_along_axis(dest, order, axis=1)
    ok_s = jnp.take_along_axis(valid, order, axis=1)
    # box_count gather/scatter with the seed axis folded: [R, H, N] flat.
    rix = jnp.arange(r, dtype=jnp.int32)[:, None]
    cnt_flat = net.box_count.reshape(r * cfg.horizon * n)
    cell = (rix * cfg.horizon + h_s) * n + d_s
    slot = cnt_flat[jnp.where(ok_s, cell, 0)] + rank
    ok_s = ok_s & (slot < c)

    sub_total = cfg.horizon * ns * c
    payload_s = jnp.take_along_axis(payload, order[:, :, None], axis=1)
    src_s = jnp.take_along_axis(src, order, axis=1)
    size_s = jnp.take_along_axis(size, order, axis=1)
    box_data = list(net.box_data)
    box_src = list(net.box_src)
    box_size = list(net.box_size)
    for j in range(p):
        dj = d_s - j * ns
        in_j = ok_s & (dj >= 0) & (dj < ns)
        # Per-seed cell index + seed-stride fold: one scatter, no
        # per-seed serialization.
        flat_j = (h_s * ns + dj) * c + jnp.where(in_j, slot, 0) + \
            rix * sub_total
        flat_jw = jnp.where(in_j, flat_j, r * sub_total).reshape(-1)
        for fi in range(cfg.payload_words):
            pl = box_data[fi * p + j]
            box_data[fi * p + j] = pl.reshape(-1).at[flat_jw].set(
                payload_s[:, :, fi].reshape(-1), mode="drop",
                unique_indices=True).reshape(pl.shape)
        box_src[j] = box_src[j].reshape(-1).at[flat_jw].set(
            src_s.reshape(-1), mode="drop",
            unique_indices=True).reshape(box_src[j].shape)
        box_size[j] = box_size[j].reshape(-1).at[flat_jw].set(
            size_s.reshape(-1), mode="drop",
            unique_indices=True).reshape(box_size[j].shape)
    cell_w = jnp.where(ok_s, cell, r * cfg.horizon * n).reshape(-1)
    box_count = cnt_flat.at[cell_w].add(
        jnp.ones_like(cell_w, dtype=jnp.int32) *
        ok_s.reshape(-1).astype(jnp.int32),
        mode="drop").reshape(net.box_count.shape)
    n_dropped = jnp.sum(jnp.take_along_axis(valid, order, axis=1) & ~ok_s,
                        axis=1).astype(jnp.int32)
    return net.replace(box_data=tuple(box_data), box_src=tuple(box_src),
                       box_size=tuple(box_size), box_count=box_count), \
        n_dropped


def _batched_inbox(cfg: EngineConfig, net: NetState, t):
    """build_inbox for the batched state ([R, ...] leaves).  No `model`
    parameter: the broadcast recompute that needs the latency model is
    unreachable here (bcast_slots == 0 by precondition)."""
    nodes = net.nodes
    n, c, f = cfg.n, cfg.inbox_cap, cfg.payload_words
    p, ns = cfg.box_split, cfg.split_n
    r = net.box_count.shape[0]
    h = t % cfg.horizon

    def rd(plane):
        # [R, H*Ns*C] -> [R, 1, Ns*C] slice at h -> [R, Ns, C]
        return jax.lax.dynamic_slice(
            plane.reshape(r, cfg.horizon, ns * c), (0, h, 0),
            (r, 1, ns * c)).reshape(r, ns, c)

    def rd_all(planes):
        if p == 1:
            return rd(planes[0])
        return jnp.concatenate([rd(pl) for pl in planes], axis=1)

    uc_data = jnp.stack(
        [rd_all(net.box_data[fi * p:(fi + 1) * p]) for fi in range(f)],
        axis=-1)                                    # [R, N, C, F]
    uc_src = rd_all(net.box_src)
    uc_size = rd_all(net.box_size)
    cnt_h = jax.lax.dynamic_slice(
        net.box_count, (0, h, 0), (r, 1, n)).reshape(r, n)
    uc_valid = jnp.arange(c)[None, None, :] < cnt_h[:, :, None]
    part_src = jnp.take_along_axis(nodes.partition, uc_src.reshape(r, -1),
                                   axis=1).reshape(r, n, c)
    deliver_ok = (~nodes.down[:, :, None]) & (
        part_src == nodes.partition[:, :, None])
    uc_valid = uc_valid & deliver_ok
    recv = jnp.sum(uc_valid, 2).astype(jnp.int32)
    rbytes = jnp.sum(jnp.where(uc_valid, uc_size, 0), 2).astype(jnp.int32)
    nodes = nodes.replace(msg_received=nodes.msg_received + recv,
                          bytes_received=nodes.bytes_received + rbytes)
    return Inbox(data=uc_data, src=uc_src, valid=uc_valid), nodes


def step_kms_batched(protocol, net: NetState, pstate, k: int,
                     hints_k=None, plane_barrier=True):
    """Batched twin of network.step_kms (seed-folded mailbox machinery;
    vmapped protocol steps and routing).  Preconditions: spill_cap == 0,
    bcast_slots == 0, per-seed times all equal and ≡ 0 (mod K), K valid
    per `network.superstep_ok` — the K-window soundness argument is
    `step_kms`'s (no in-window consumption below the latency floor),
    broadcast-free by this engine's scope.

    `plane_barrier=False` disables the read-write ordering barrier (the
    same-process A/B knob — results are bit-identical either way, per
    tests/test_batched.py::test_plane_barrier_bit_identity; the barrier
    only changes whether XLA can update the planes in place)."""
    cfg, model = protocol.cfg, protocol.latency
    assert cfg.spill_cap == 0 and cfg.bcast_slots == 0
    if hints_k is not None and len(hints_k) != k:
        raise ValueError(f"hints_k must have {k} entries, got "
                         f"{len(hints_k)}")
    r = net.box_count.shape[0]
    t = net.time[0]
    with jax.named_scope(STAGE_INBOX):
        # Chaos-plane hook (see network.step_kms): one stateless
        # window-entry application; the [N] fault vectors broadcast over
        # the [R, N] node leaves, and K-aligned transitions keep the
        # window state constant.
        af = getattr(protocol, "apply_faults", None)
        if af is not None:
            net = af(net, t)

        inboxes = []
        for i in range(k):
            # `t + i if i else t`: keeps the i == 0 trace free of a dead
            # `t + 0` eqn (the jaxpr_eqns budgets pin the K == 2 program
            # at exactly the historical step_2ms_batched op count).
            ib, nodes = _batched_inbox(cfg, net, t + i if i else t)
            net = net.replace(nodes=nodes)
            inboxes.append(ib)

    # Order every later plane WRITE after all K inbox READS by threading
    # the planes through one optimization_barrier with the inbox values.
    # Without this, XLA's copy-insertion cannot prove the scatters run
    # after the slices whenever a phase-hinted step's outbox is
    # structurally independent of its inbox, and it inserts a FULL COPY
    # of every ring plane per superstep — measured 40 -> 2 plane copies
    # in the compiled while body (tools/carry_audit.py — now enforced as
    # the carry_copy budget in wittgenstein_tpu/analysis), the "scan
    # carry DUS churn" item of reports/PROFILE_r4.md.  The barrier is
    # pure ordering: no data is copied and results are bit-identical
    # with it on or off
    # (tests/test_batched.py::test_plane_barrier_bit_identity).
    if plane_barrier:
        (inboxes, bd, bs, bz, bc) = jax.lax.optimization_barrier(
            (inboxes, net.box_data, net.box_src, net.box_size,
             net.box_count))
        net = net.replace(box_data=bd, box_src=bs, box_size=bz,
                          box_count=bc)

    def pstep(ps, nodes_r, inbox_r, seed, tt, hints):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), tt)
        if hints is None:
            return protocol.step(ps, nodes_r, inbox_r, tt, key)
        return protocol.step(ps, nodes_r, inbox_r, tt, key, hints=hints)

    outs = []
    with jax.named_scope(STAGE_PROTOCOL):
        for i in range(k):
            hint = None if hints_k is None else hints_k[i]
            pstate, nodes, out = jax.vmap(
                lambda ps, nd, ib, sd, tt=(t + i if i else t), hh=hint:
                pstep(ps, nd, ib, sd, tt, hh))(
                pstate, net.nodes, inboxes[i], net.seed)
            net = net.replace(nodes=nodes)
            outs.append(out)

    h = t % cfg.horizon
    n = cfg.n
    with jax.named_scope(STAGE_BIN):
        net = net.replace(box_count=jax.lax.dynamic_update_slice(
            net.box_count, jnp.zeros((r, k, n), jnp.int32), (0, h, 0)))

    # Routing per seed (vmapped — elementwise + latency model), then ONE
    # folded bin for all K ms across all seeds.
    def route(net_r, out_r, tt):
        return _route_unicast(cfg, model, net_r, out_r, tt)

    with jax.named_scope(STAGE_ROUTE):
        batches = []
        for i, out in enumerate(outs):
            net, b, _ = jax.vmap(
                lambda nr, orr, tt=(t + i if i else t):
                route(nr, orr, tt))(net, out)
            batches.append(b)
        terms = [jnp.sum(b[6], axis=1) for b in batches]
        n_clamped = terms[0]
        for tm in terms[1:]:
            n_clamped = n_clamped + tm
        n_clamped = n_clamped.astype(jnp.int32)
    with jax.named_scope(STAGE_BIN):
        src = jnp.concatenate([b[0] for b in batches], axis=1)
        dest = jnp.concatenate([b[1] for b in batches], axis=1)
        arrival = jnp.concatenate([b[2] for b in batches], axis=1)
        payload = jnp.concatenate([b[3] for b in batches], axis=1)
        size = jnp.concatenate([b[4] for b in batches], axis=1)
        valid = jnp.concatenate([b[5] for b in batches], axis=1)
        net, n_dropped = _batched_bin(cfg, net, t, src, dest, arrival,
                                      payload, size, valid)
    net = net.replace(dropped=net.dropped + n_dropped,
                      clamped=net.clamped + n_clamped,
                      time=net.time + k)
    return net, pstate


def step_2ms_batched(protocol, net: NetState, pstate, hints2=(None, None),
                     plane_barrier=True):
    """The K == 2 seed-folded superstep (`step_kms_batched`) — kept as a
    named entry point, like `network.step_2ms`: K == 2 needs no latency
    floor and no self-send declaration."""
    return step_kms_batched(protocol, net, pstate, 2,
                            hints_k=list(hints2),
                            plane_barrier=plane_barrier)


def _next_work_batched(protocol, net: NetState, pstate, t):
    """Batched next-event oracle for the seed-folded engine: the MIN
    over the seed batch of each run's earliest work ms — a window is
    skipped only when EVERY seed is quiet, which keeps the batch in
    lockstep (the folded mailbox scatter requires it).  bcast_slots == 0
    by the engine's precondition, so the oracle is just the mailbox
    term + the protocol timers (network.next_work's (a) and (c))."""
    cfg = protocol.cfg
    far = jnp.int32(FAR_FUTURE)
    rows = jnp.arange(cfg.horizon, dtype=jnp.int32)
    row_any = jnp.any(net.box_count > 0, axis=-1)              # [R, H]
    nxt = jnp.min(jnp.where(row_any, t + (rows[None, :] - t) % cfg.horizon,
                            far))
    # next_action_time exists by fast_forward_chunk_batched's
    # fast_forward_ok precondition — no no-oracle mode here.
    nat = protocol.next_action_time
    proto_next = jnp.min(jax.vmap(
        lambda ps, nd: nat(ps, nd, t))(pstate, net.nodes))
    return jnp.maximum(jnp.minimum(nxt, proto_next), t).astype(jnp.int32)


def fast_forward_chunk_batched(protocol, ms: int, plane_barrier=True,
                               superstep: int = 2):
    """Quiet-window fast-forwarding for the seed-folded superstep
    engine: a `lax.while_loop` whose body is one `step_kms_batched` pass
    followed by a batch-min oracle jump, floored to K-ALIGNED offsets so
    every loop entry satisfies the fused window's entry-time contract
    (an unaligned oracle target lands up to K-1 quiet ms early — sound,
    one extra no-op window at worst).  Bit-identical to
    `scan_chunk_batched` (tests/test_fast_forward.py); preconditions are
    the batched engine's plus `network.fast_forward_ok`.  Returns
    ``run(net, pstate) -> (net, pstate, stats)`` with the same skip
    accounting as `network.fast_forward_chunk`."""
    # Shared gate first (spill-free + no phase hints + the K-window
    # proof — the remedies live in network.check_chunk_config), then the
    # batched engine's own narrower preconditions.
    check_chunk_config(protocol, ms, superstep=superstep,
                       fast_forward=True)
    _check_batched_scope(protocol, ms, superstep)
    if not fast_forward_ok(protocol):
        raise ValueError("fast_forward_chunk_batched needs a protocol "
                         "implementing next_action_time (without it no "
                         "window is provably quiet and the loop would "
                         "degenerate to a slower dense scan)")
    k = superstep

    def run(net, pstate):
        t_end = net.time[0] + ms

        def cond(carry):
            return carry[0].time[0] < t_end

        def body(carry):
            net, ps, skipped, jumps = carry
            net, ps = step_kms_batched(protocol, net, ps, k,
                                       plane_barrier=plane_barrier)
            t1 = net.time[0]
            nw = jnp.clip(_next_work_batched(protocol, net, ps, t1),
                          t1, t_end)
            dt = (nw - t1) - (nw - t1) % k    # keep entry times K-aligned
            net = net.replace(time=net.time + dt)
            return (net, ps, skipped + dt,
                    jumps + (dt > 0).astype(jnp.int32))

        z = jnp.asarray(0, jnp.int32)
        net, pstate, skipped, jumps = jax.lax.while_loop(
            cond, body, (net, pstate, z, z))
        return net, pstate, {"skipped_ms": skipped, "jump_count": jumps}

    return run


def _check_batched_scope(protocol, ms, superstep):
    """The batched engine's own preconditions, layered on the shared
    gate: broadcast-free (the seed-folded mailbox machinery has no
    broadcast table path) and a K-aligned chunk."""
    if (superstep < 2 or ms % superstep or protocol.cfg.spill_cap
            or protocol.cfg.bcast_slots
            or not superstep_ok(protocol, superstep)):
        raise ValueError(
            f"the batched engine needs a chunk that is a multiple of "
            f"superstep={superstep} (>= 2; got chunk {ms}) and a "
            "spill-free, broadcast-free, superstep-eligible protocol "
            "(core/batched.py scope; see network.check_chunk_config for "
            "the per-constraint remedies)")


def scan_chunk_batched(protocol, ms: int, t0_mod=None, plane_barrier=True,
                       fast_forward=False, superstep: int = 2):
    """Batched twin of scan_chunk(superstep=K) for vmap-batched state
    (leaves [R, ...]); K defaults to the universally-valid 2.  Same
    phase-specialization contract; chunk must be K-aligned and a
    multiple of the (K-adjusted) schedule lcm when t0_mod is given.
    `plane_barrier` — see `step_kms_batched`.  `fast_forward=True` swaps
    the dense scan for the quiet-window while loop
    (`fast_forward_chunk_batched`, stats dropped); incompatible with
    t0_mod for the same reason as `network.scan_chunk`."""
    k = superstep
    if fast_forward:
        check_chunk_config(protocol, ms, t0_mod=t0_mod, superstep=k,
                           fast_forward=True)
        base_ff = fast_forward_chunk_batched(protocol, ms,
                                             plane_barrier=plane_barrier,
                                             superstep=k)

        def run_ff(net, pstate):
            net, pstate, _ = base_ff(net, pstate)
            return net, pstate

        return run_ff
    check_chunk_config(protocol, ms, t0_mod=t0_mod, superstep=k)
    _check_batched_scope(protocol, ms, k)
    lcm = getattr(protocol, "schedule_lcm", None) if t0_mod is not None \
        else None
    if lcm and lcm % k:
        import math
        lcm = lcm * k // math.gcd(lcm, k)
    if lcm:
        if ms % lcm:
            raise ValueError(f"chunk {ms} not a multiple of lcm {lcm}")
        sched = getattr(protocol, "schedule_lcm")
        hints = [protocol.phase_hints((t0_mod + dt) % sched)
                 for dt in range(lcm)]
        blocks = ms // lcm

        def run_spec(net, pstate):
            def body(carry, _):
                net, ps = carry
                for i in range(0, len(hints), k):
                    net, ps = step_kms_batched(
                        protocol, net, ps, k, hints_k=hints[i:i + k],
                        plane_barrier=plane_barrier)
                return (net, ps), ()
            (net, pstate), _ = jax.lax.scan(body, (net, pstate),
                                            length=blocks)
            return net, pstate

        return run_spec

    def run(net, pstate):
        def body(carry, _):
            return step_kms_batched(protocol, *carry, k,
                                    plane_barrier=plane_barrier), ()
        (net2, p2), _ = jax.lax.scan(body, (net, pstate), length=ms // k)
        return net2, p2

    return run
