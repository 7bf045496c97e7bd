"""P2P peer graphs + flood forwarding, fully vectorized.

Reference surface (SURVEY.md §2.1):
  - `P2PNetwork.setPeers` builds a random peer graph with either a minimum
    per-node degree or a target average degree (core/P2PNetwork.java:26-55);
    links are symmetric and deduplicated via an edge set (:63-113).
  - `P2PNode.peers` is an adjacency list (core/P2PNode.java:9-28).
  - `FloodMessage.action` forwards a newly received flood to all peers except
    the sender, in shuffled order, with `localDelay` before the first send and
    `delayBetweenPeers` between consecutive peers
    (core/messages/FloodMessage.java:47-54, P2PNetwork.sendPeers :127-132).

TPU-native design: the adjacency is a fixed-shape `[N, D]` int32 matrix
(-1 = empty slot) built in one shot from counter-based draws — construction is
deterministic per seed, jittable, and vmappable over seeds.  The reference's
sequential "top-up until everyone has >= c links" loop
(P2PNetwork.java:45-55) is inherently serial; we instead have every node draw
its quota at once and symmetrize, which preserves the invariants that matter
(min degree >= c for the minimum variant, expected degree ~= c for the average
variant, uniformly random partners) while being O(1) depth — a statistical
match, not a bit-for-bit one (SURVEY.md §7.4.3 sets that bar).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import prng

TAG_P2P = 0x50325030  # domain separation for peer-graph draws
TAG_SHUF = 0x50325346  # flood fan-out shuffle draws

_BIG = np.int32(0x7FFFFFFF)   # numpy: a jnp constant would init the backend at import


def _scatter_adjacency(src, dst, keep, n, max_degree):
    """Turn a kept directed-edge list into a `[N, D]` adjacency + degree.

    Sorts edges by (src, dst), drops duplicates, ranks each kept edge within
    its source group (the same rank-in-group trick as the mailbox router,
    network.enqueue_unicast), and scatters `dst` into the source's next free
    slot.  Edges beyond `max_degree` are dropped and counted.
    """
    m = src.shape[0]
    src_k = jnp.where(keep, src, _BIG)
    dst_k = jnp.where(keep, dst, _BIG)
    o1 = jnp.argsort(dst_k, stable=True)
    order = o1[jnp.argsort(src_k[o1], stable=True)]
    src_s, dst_s = src_k[order], dst_k[order]

    dup = (src_s == jnp.roll(src_s, 1)) & (dst_s == jnp.roll(dst_s, 1))
    dup = dup.at[0].set(False)
    kept = (src_s != _BIG) & ~dup

    idx = jnp.arange(m, dtype=jnp.int32)
    # Rank among *kept* entries within each src group: cumulative kept count
    # minus the kept count at the group start.
    ckept = jnp.cumsum(kept.astype(jnp.int32))
    new_grp = (src_s != jnp.roll(src_s, 1)).at[0].set(True)
    grp_base = jax.lax.cummax(jnp.where(new_grp, ckept - kept, 0))
    rank = ckept - kept - grp_base

    ok = kept & (rank < max_degree)
    src_w = jnp.where(ok, src_s, n)                 # n is OOB -> dropped
    rank_w = jnp.where(ok, rank, max_degree)
    peers = jnp.full((n, max_degree), -1, jnp.int32)
    peers = peers.at[src_w, rank_w].set(dst_s, mode="drop")
    degree = jnp.zeros((n,), jnp.int32).at[src_w].add(
        ok.astype(jnp.int32), mode="drop")
    overflow = jnp.sum(kept & ~ok).astype(jnp.int32)
    return peers, degree, overflow


def build_peer_graph(seed, n: int, connection_count: int, minimum: bool = True,
                     max_degree: int | None = None):
    """Vectorized `P2PNetwork.setPeers` (core/P2PNetwork.java:26-55).

    minimum=True : every node draws `connection_count` uniform partners; the
                   symmetric closure gives min degree >= connection_count
                   (reference invariant) and mean ~= 2c (the reference's
                   shuffled top-up lands between c and 2c).
    minimum=False: n*c/2 uniform pairs (mean degree ~= c, the reference
                   invariant), then every node below min(3, c) draws up to 3
                   partners so nobody is isolated (:45-55).

    Returns (peers [N, D] int32 with -1 padding, degree [N] int32,
    overflow int32 scalar — symmetric-closure links dropped because a node's
    D slots were full; size D generously or assert overflow == 0).
    """
    if connection_count >= n:
        raise ValueError(
            f"wrong configuration: nodes={n}, "
            f"connection target={connection_count}")
    seed = prng.hash2(jnp.asarray(seed, jnp.int32), TAG_P2P)
    ids = jnp.arange(n, dtype=jnp.int32)

    def draw_partners(sub, count):
        # `count` *distinct* uniform partners per node: draw in [0, n-1) and
        # skip self, then repair within-row duplicates by redrawing them a
        # few rounds (collision probability decays ~(c^2/n)^rounds, so four
        # rounds make "fewer than c distinct partners" vanishingly rare —
        # preserving the reference's min-degree invariant, P2PNetwork:45-55).
        cols = []
        for j in range(count):
            p = prng.uniform_int(prng.hash2(seed, sub * 1000 + j), ids, n - 1)
            cols.append(p + (p >= ids))
        part = jnp.stack(cols, axis=1)                # [N, count]
        for r in range(1, 5):
            dup = jnp.zeros(part.shape, bool)
            for j in range(1, count):
                dup = dup.at[:, j].set(
                    jnp.any(part[:, :j] == part[:, j:j + 1], axis=1))
            redraw = prng.uniform_int(
                prng.hash2(seed, sub * 1000 + 500 + r),
                ids[:, None] * count + jnp.arange(count)[None, :], n - 1)
            redraw = redraw + (redraw >= ids[:, None])
            part = jnp.where(dup, redraw, part)
        return part

    if minimum:
        c = connection_count
        if max_degree is None:
            max_degree = max(4 * c, c + 16)
        part = draw_partners(1, c)                    # [N, c]
        a = jnp.repeat(ids, c)
        b = part.reshape(-1)
        src = jnp.concatenate([a, b])
        dst = jnp.concatenate([b, a])
        keep = jnp.ones_like(src, dtype=bool)
    else:
        c = connection_count
        if max_degree is None:
            max_degree = max(4 * c, c + 16)
        npairs = max(1, (n * c) // 2)
        pid = jnp.arange(npairs, dtype=jnp.int32)
        pa = prng.uniform_int(prng.hash2(seed, 7001), pid, n)
        pb = prng.uniform_int(prng.hash2(seed, 7002), pid, n)
        # Guaranteed floor: nodes whose pair-phase degree is below min(3, c)
        # draw 3 partners (the reference tops up below-minimum nodes only).
        deg0 = (jnp.zeros((n,), jnp.int32).at[pa].add(1, mode="drop")
                .at[pb].add(1, mode="drop"))
        lonely = deg0 < min(3, c)
        extra = draw_partners(2, min(3, max(1, c)))   # [N, e]
        e = extra.shape[1]
        xa = jnp.repeat(ids, e)
        xb = extra.reshape(-1)
        xkeep = jnp.repeat(lonely, e)
        src = jnp.concatenate([pa, pb, xa, xb])
        dst = jnp.concatenate([pb, pa, xb, xa])
        keep = jnp.concatenate([pa != pb, pa != pb, xkeep, xkeep])

    return _scatter_adjacency(src, dst, keep, n, max_degree)


def avg_peers(degree):
    """`P2PNetwork.avgPeers` (core/P2PNetwork.java:115-125)."""
    return jnp.sum(degree) // jnp.maximum(1, degree.shape[0])


def disconnect(peers, degree, node_mask):
    """Drop every link touching a masked node (`P2PNetwork.disconnect`,
    core/P2PNetwork.java:57-61): removes them as sources *and* from everyone
    else's peer lists (slots become -1; degree recomputed)."""
    dead_peer = jnp.where(peers >= 0, node_mask[jnp.maximum(peers, 0)], False)
    peers = jnp.where(dead_peer | node_mask[:, None], -1, peers)
    degree = jnp.sum(peers >= 0, axis=1).astype(jnp.int32)
    return peers, degree


def shuffled_order(seed, t, n: int, d: int):
    """Per-node pseudo-random slot permutation — the analogue of the
    `Collections.shuffle(dest, rd)` in sendPeers/action
    (P2PNetwork.java:127-132).  order[i, k] = the peer slot visited k-th in
    node i's shuffled order at time t (one argsort total)."""
    flat = jnp.arange(n * d, dtype=jnp.int32).reshape(n, d)
    pri = prng.uniform_u32(prng.hash3(seed, TAG_SHUF, t), flat)
    return jnp.argsort(pri, axis=1).astype(jnp.int32)


def flood_fanout(cfg, peers, forward, exclude_src, payload, seed, t,
                 local_delay=0, delay_between=0, size=1):
    """Outbox fields for `FloodMessage.action`-style forwarding.

    For every node with `forward[i]` set: send `payload[i]` to all its peers
    except `exclude_src[i]`, in a shuffled order, the k-th in that order
    delayed by `local_delay + k * delay_between` ms
    (core/messages/FloodMessage.java:47-54).

    Requires cfg.out_deg == peers.shape[1].  Returns (dest, payload, size,
    delay) arrays shaped for `Outbox`.
    """
    n, d = peers.shape
    assert cfg.out_deg == d, (cfg.out_deg, d)
    ok = forward[:, None] & (peers >= 0) & (peers != exclude_src[:, None])
    dest = jnp.where(ok, peers, -1)
    # Rank among *sent* slots only: count how many sent slots precede mine
    # in the shuffled order (excluded peers must not leave delay gaps).
    order = shuffled_order(seed, t, n, d)
    sent_sorted = jnp.take_along_axis(ok, order, axis=1)
    pos_sorted = jnp.cumsum(sent_sorted.astype(jnp.int32), axis=1) - 1
    pos = jnp.zeros((n, d), jnp.int32).at[
        jnp.arange(n)[:, None], order].set(pos_sorted)
    delay = local_delay + jnp.maximum(pos, 0) * delay_between
    out_payload = jnp.broadcast_to(payload[:, None, :],
                                   (n, d, payload.shape[-1]))
    out_size = jnp.full((n, d), size, jnp.int32)
    return dest, out_payload, out_size, delay.astype(jnp.int32)
