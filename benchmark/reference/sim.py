"""Handel, one seed at a time, one simulated millisecond at a time, in
plain numpy on the host.

Written from the protocol's stated semantics (Handel.java as the
configurations' sources give it, with the bounded shapes the
configurations fix), sharing no code and no state with the program.
Aggregates are packed 8 bits to a byte; each node's verification queue
is kept sorted by reception rank.

The network.  A message sent at ms t arrives at t + 1 + latency, the
latency cut to [1, horizon - 2] (a cut counts as `clamped`).  Each
(node, arrival ms) holds at most `inbox_cap` messages, in the order they
were sent (by send ms, then sender, then the sender's outbox slot); one
more is `dropped`.  Messages to a node that is down are not delivered
and not lost.  A sender counts every message it addresses (msg_sent,
bytes_sent), a receiver every message it is handed (msg_received,
bytes_received).  Per ms: deliver, run every node's step, send.

A node's step at ms t (nodes start at 0; a node is active from ms 1
while it is up):

1. Receive.  A done node counts each message it is handed as filtered
   and drops it.  Otherwise a message (level l, payload) from s joins
   the queue: one entry per (sender, level), the latest message wins;
   the queue keeps the `queue_cap` entries of lowest rank (an entry
   pushed out is `evicted`).  Rank: node i ranks sender s by a keyed
   bijection of the node ids under (seed, i); exact mode adds N once i
   has verified something from s.
2. Finish a verification whose pairing time has passed: merge it into
   the level's best aggregate, mark newly complete upper levels for the
   fast path, and be done once the total reaches the threshold.
3. On each pairing tick with nothing in verification: drop queue
   entries that cannot improve their level; per level pick, among the
   improving entries within `window` ranks of the level's lowest queued
   rank, the best score (ties: lower rank), else the lowest-ranked
   improving entry; choose one such level by a draw, and verify that
   entry for `pairing_time` ms.  The window doubles (bounded by the
   level's size and `window_max`).
4. On each dissemination tick: send each open level's aggregate to the
   next peer of its emission order (exact mode skips peers known to be
   finished, looking `emission_lookahead` ahead); done nodes keep going
   for `extra_cycle` ticks.  Every ms: send the lowest level marked for
   the fast path to `fast_path` peers.

Exact mode keeps aggregates as sets of signers (bitsets).  Cardinal mode
keeps, per level, the count of the best verified aggregate, and an
aggregate replaces the level's best when its count is larger.
"""

from __future__ import annotations

import numpy as np

from . import world

#: The defaults of the protocol's parameters (Handel.HandelParameters as
#: the configurations take them); a configuration states the rest.
DEFAULTS = dict(pairing_time=3, level_wait_time=50, extra_cycle=10,
                dissemination_period_ms=10, fast_path=10, nodes_down=0,
                window_initial=16, window_min=1, window_max=128,
                queue_cap=16, inbox_cap=16, horizon=512,
                emission_lookahead=8, mode="exact",
                desynchronized_start=0, byzantine_suicide=False,
                hidden_byzantine=False)
#: what this reference simulates; anything else is refused
PLACEMENT = "CITIES_SPEED=CONSTANT_TOR=0.00"
LATENCY = "NetworkLatencyByDistanceWJitter"

#: the registry class name this module simulates (`benchmark/check.py`
#: finds it by this name); Handel's cardinal mode is the same class
PROTOCOL = "Handel"
NODE_FIELDS = ("done_at", "msg_sent", "msg_received", "bytes_sent",
               "bytes_received")
PROTO_FIELDS = ("sigs_checked", "msg_filtered")
PROTO_LOSSES = ("evicted",)
#: the configurations' parameters as the CPU tests cut them: 64 nodes,
#: 10% down, the threshold 0.99 of the live
TINY = dict(node_count=64, nodes_down=6, threshold=57)
BIG = 1 << 40


def tables(evaluate):
    """What the device evaluates for the reference: the latency tables
    (`world.latency_tables`)."""
    return world.latency_tables(evaluate)


def _params(params: dict) -> dict:
    p = dict(DEFAULTS)
    p.update(params)
    n = p["node_count"]
    if n & (n - 1) or n < 2:
        raise ValueError("node_count must be a power of two")
    p.setdefault("threshold", int(n * 0.99))
    if p["threshold"] is None:
        p["threshold"] = int(n * 0.99)
    if (p.get("node_builder_name") != PLACEMENT or
            p.get("network_latency_name") != LATENCY):
        raise ValueError(f"the reference places nodes by {PLACEMENT} "
                         f"and times messages by {LATENCY}")
    if (p["desynchronized_start"] or p["byzantine_suicide"] or
            p["hidden_byzantine"]):
        raise ValueError("the reference covers honest, synchronous runs")
    if p["mode"] == "exact" and n > 32768:
        raise ValueError("exact mode stores emission lists: N <= 32768")
    return p


class Network:
    """The mailbox: what arrives when, and the engine's counters."""

    def __init__(self, n, seed, down, city, tables, horizon, cap, k, nw):
        self.n, self.seed, self.down = n, seed, down
        self.city, self.tables = city, tables
        self.horizon, self.cap, self.k, self.nw = horizon, cap, k, nw
        self.arrivals = {}        # ms -> [batches of (src, dst, words, size)]
        self.filled = {}          # ms -> messages held per receiver [N]
        self.c = {f: np.zeros(n, np.int64) for f in NODE_FIELDS[1:]}
        self.dropped = self.clamped = 0

    def deliver(self, t):
        """[N, cap] (src, words, valid) of what arrives at ms t."""
        n, cap = self.n, self.cap
        batches = self.arrivals.pop(t, [])
        self.filled.pop(t, None)
        src = np.zeros((n, cap), np.int64)
        valid = np.zeros((n, cap), bool)
        words = np.zeros((n, cap, self.nw), np.int64)
        if batches:
            s, d, w, z = (np.concatenate(a) for a in zip(*batches))
            order = np.argsort(d, kind="stable")
            s, d, w, z = s[order], d[order], w[order], z[order]
            first = np.searchsorted(d, d, side="left")
            slot = np.arange(len(d)) - first
            src[d, slot], words[d, slot], valid[d, slot] = s, w, True
            self.c["msg_received"] += np.bincount(d, minlength=n)
            self.c["bytes_received"] += np.bincount(d, z, minlength=n) \
                .astype(np.int64)
        return src, words, valid

    def send(self, t, dest, words, size):
        n, k = self.n, dest.shape[1]
        src = np.repeat(np.arange(n), k)
        col = np.tile(np.arange(k), n)
        dst = dest.reshape(-1)
        w = words.reshape(n * k, -1)
        z = size.reshape(-1)
        want = (dst >= 0) & ~self.down[src]
        self.c["msg_sent"] += np.bincount(src[want], minlength=n)
        self.c["bytes_sent"] += np.bincount(src[want], z[want],
                                            minlength=n).astype(np.int64)
        src, col, dst, w, z = (a[want] for a in (src, col, dst, w, z))
        q = world.draw_below(world.mix3(self.seed, world.TAG_LATENCY, t),
                             src * self.k + col, 100)
        lat = world.latency_ms(self.tables, self.city, src, dst, q)
        keep = ~self.down[dst]
        src, dst, w, z, lat = (a[keep] for a in (src, dst, w, z, lat))
        self.clamped += int(np.sum(lat > self.horizon - 2))
        arrive = t + 1 + np.clip(lat, 1, self.horizon - 2)
        for ms in np.unique(arrive):
            at = arrive == ms
            s, d = src[at], dst[at]
            held = self.filled.setdefault(int(ms), np.zeros(n, np.int64))
            order = np.argsort(d, kind="stable")
            ds = d[order]
            rank = np.empty(len(d), np.int64)
            rank[order] = np.arange(len(d)) - np.searchsorted(ds, ds)
            ok = held[d] + rank < self.cap
            self.dropped += int(np.sum(~ok))
            held += np.bincount(d[ok], minlength=n)
            self.arrivals.setdefault(int(ms), []).append(
                (s[ok], d[ok], w[at][ok], z[at][ok]))


class Handel:
    """Both modes; exact-mode aggregates are packed bitsets [N, N/8]."""

    def __init__(self, p, seed, down):
        self.p, self.seed, self.down = p, seed, down
        n = self.n = p["node_count"]
        self.exact = p["mode"] == "exact"
        self.bits = n.bit_length() - 1
        self.L = self.bits + 1
        self.half = np.array([0] + [1 << (l - 1) for l in range(1, self.L)])
        self.K = (self.L - 1) + p["fast_path"]
        self.ids = np.arange(n)
        Q = p["queue_cap"]
        self.done_at = np.zeros(n, np.int64)
        self.q_from = np.full((n, Q), -1, np.int64)
        self.q_lvl = np.zeros((n, Q), np.int64)
        self.q_rank = np.zeros((n, Q), np.int64)
        self.pos = np.zeros((n, self.L), np.int64)
        self.window = np.full(n, p["window_initial"], np.int64)
        self.cycles = np.full(n, p["extra_cycle"], np.int64)
        self.pend_from = np.full(n, -1, np.int64)
        self.pend_level = np.zeros(n, np.int64)
        self.pend_at = np.zeros(n, np.int64)
        self.fast = np.zeros(n, np.int64)       # levels marked, as bits
        self.sigs_checked = np.zeros(n, np.int64)
        self.msg_filtered = np.zeros(n, np.int64)
        self.evicted = 0
        self.rank_key = world.mix3(seed, world.TAG_RANK, self.ids)
        if self.exact:
            self._init_exact()
        else:
            self.best = np.zeros((n, self.L), np.int64)
            self.q_val = np.zeros((n, Q), np.int64)
            self.pend_val = np.zeros(n, np.int64)

    # ------------------------------------------------------- geometry

    def level_range(self, i, l):
        """Base of node i's level-l peers: the half of its 2^l-aligned
        block that does not hold i."""
        h = self.half[l]
        return (i & ~(2 * h - 1)) + np.where(i & h, 0, h)

    def rank(self, i, s):
        return world.permute(self.rank_key[i], s, self.bits)

    # ------------------------------------------------------- exact sets

    def _init_exact(self):
        n, L, p = self.n, self.L, self.p
        self.B = n // 8 if n >= 8 else 1
        own = np.zeros((n, n), bool)
        own[self.ids, self.ids] = True
        self.ver = self._pack(own)
        self.agg = np.zeros_like(self.ver)
        self.finished = np.zeros_like(self.ver)
        self.demoted = np.zeros((n, n), bool)
        self.q_sig = np.zeros((n, p["queue_cap"], self.B), np.uint8)
        self.pend_sig = np.zeros_like(self.ver)
        rounds = p["horizon"] // max(1, p["dissemination_period_ms"]) + 2
        self.snap = np.zeros((n, rounds, self.B), np.uint8)
        # level masks [L, N, B], and each node's emission lists: level
        # l's receivers at columns [h, 2h), ordered by the rank THEY give
        # this node, ties by id
        self.lmask = np.zeros((L, n, self.B), np.uint8)
        self.emission = np.zeros((n, n), np.int64)
        for l in range(1, L):
            h = self.half[l]
            base = self.level_range(self.ids, l)
            peers = base[:, None] + np.arange(h)[None, :]
            m = np.zeros((n, n), bool)
            m[np.repeat(self.ids, h), peers.reshape(-1)] = True
            self.lmask[l] = self._pack(m)
            r = self.rank(peers, self.ids[:, None])
            order = np.lexsort((peers, r), axis=1)
            self.emission[:, h:2 * h] = np.take_along_axis(peers, order, 1)

    def _pack(self, b):
        return np.packbits(b, axis=-1, bitorder="little")

    @staticmethod
    def _count(x):
        return np.bitwise_count(x).sum(-1, dtype=np.int64)

    def _has(self, sets, i, s):
        """Whether node s is in row i of packed `sets`."""
        return ((sets[i, s >> 3] >> (s & 7).astype(np.uint8)) & 1) == 1

    def _add(self, sets, i, s):
        np.bitwise_or.at(sets, (i, s >> 3),
                         (np.uint8(1) << (s & 7).astype(np.uint8)))

    def _per_level(self, sets, rows=None):
        """[R, L] members of each level's range in `sets` ([R, B])."""
        rows = self.ids if rows is None else rows
        out = np.zeros((len(rows), self.L), np.int64)
        for l in range(1, self.L):
            out[:, l] = self._count(sets & self.lmask[l][rows])
        return out

    # ------------------------------------------------------- the ms

    def step(self, t, src, words, valid):
        p = self.p
        active = ~self.down & (t >= 1)
        self._receive(t, src, words, valid)
        self._finish(t)
        due = (active & (self.pend_from < 0) &
               ((t - 1) % p["pairing_time"] == 0))
        self._pick(t, np.flatnonzero(due))
        return self._send(t, active)

    def _receive(self, t, src, words, valid):
        n, Q, L = self.n, self.p["queue_cap"], self.L
        done = self.done_at > 0
        self.msg_filtered += np.sum(valid & done[:, None], axis=1)
        ok = valid & ~done[:, None]
        r = np.flatnonzero(ok.any(1))        # nodes with something to queue
        if not len(r):
            return
        ok, src, words = ok[r], src[r], words[r]
        lvl = np.clip(words[..., 0], 0, L - 1)
        i = np.broadcast_to(r[:, None], src.shape)
        if self.exact:
            fin = ok & ((words[..., 1] & 1) == 1)
            self._add(self.finished, i[fin], src[fin])
            rank = self.rank(i, src) + n * self.demoted[i, src]
        else:
            rank = self.rank(i, src)
        # the latest message per (sender, level) wins, and replaces the
        # queued entry of the same key
        key = np.where(ok, src * L + lvl, -1)
        later = np.triu(np.ones((key.shape[1],) * 2, bool), 1)
        dup = ((key[:, :, None] == key[:, None, :]) & later &
               ok[:, None, :]).any(2)
        inc = ok & ~dup
        q_from = self.q_from[r]
        qkey = np.where(q_from >= 0, q_from * L + self.q_lvl[r], -2)
        replaced = ((qkey[:, :, None] == key[:, None, :]) &
                    inc[:, None, :]).any(2)
        keep_q = (q_from >= 0) & ~replaced
        c = src.shape[1]
        cand_from = np.concatenate([np.where(keep_q, q_from, -1),
                                    np.where(inc, src, -1)], 1)
        cand_rank = np.concatenate([self.q_rank[r], rank], 1)
        sort_key = np.where(cand_from >= 0,
                            cand_rank * (Q + c + 1) + np.arange(Q + c), BIG)
        order = np.argsort(sort_key, axis=1, kind="stable")[:, :Q]
        take = lambda a: np.take_along_axis(a, order, 1)  # noqa: E731
        self.q_from[r] = take(cand_from)
        self.q_rank[r] = take(cand_rank)
        self.q_lvl[r] = take(np.concatenate([self.q_lvl[r], lvl], 1))
        kept_old = np.sum((order < Q) & (self.q_from[r] >= 0))
        self.evicted += int(keep_q.sum() - kept_old)
        # each kept entry's payload, as a row of the old queue or a message
        rows = np.arange(len(r))[:, None]
        old = order < Q
        if self.exact:
            sig = np.zeros((len(r), Q, self.B), np.uint8)
            sig[old] = self.q_sig[r[:, None], np.minimum(order, Q - 1)][old]
            m = ~old & (take(cand_from) >= 0)
            j = (order - Q)[m]
            rr = np.broadcast_to(rows, order.shape)[m]
            sig[m] = (self.snap[src[rr, j], words[rr, j, 2]] &
                      self.lmask[lvl[rr, j], r[rr]])
            self.q_sig[r] = sig
        else:
            val = np.minimum(np.maximum(words[..., 1], 0), self.half[lvl])
            self.q_val[r] = take(np.concatenate([self.q_val[r], val], 1))

    def _finish(self, t):
        """Apply the verifications whose pairing time is over."""
        d = np.flatnonzero((self.pend_from >= 0) & (t >= self.pend_at))
        if not len(d):
            return
        lvl = self.pend_level[d]
        half = self.half[lvl]
        if self.exact:
            self._add(self.ver, d, self.pend_from[d])
            lm = self.lmask[lvl, d]
            sig = self.pend_sig[d]
            old = self.agg[d] & lm
            ver_l = self.ver[d] & lm
            better = self._count(sig | ver_l) > self._count(ver_l)
            overlap = (old & sig).any(-1)
            new = np.where((better & overlap)[:, None], sig,
                           np.where(better[:, None], old | sig, old))
            self.agg[d] = (self.agg[d] & ~lm) | new
            total = self.agg[d] | self.ver[d]
            per = self._per_level(total, d)
            complete = (per[np.arange(len(d)), lvl] >= half) & (half > 0)
            card = self._count(total)
        else:
            val = self.pend_val[d]
            cur = self.best[d, lvl]
            better = val > cur
            self.best[d[better], lvl[better]] = val[better]
            per = self.best[d]
            complete = better & (val >= half) & (half > 0)
            card = 1 + per.sum(1)
        if self.p["fast_path"] > 0:
            out = 1 + np.cumsum(per, 1) - per     # own + levels below
            levels = np.arange(self.L)[None, :]
            mark = ((out >= self.half[None, :]) & (self.half > 0)[None, :] &
                    (levels > lvl[:, None]) & complete[:, None])
            self.fast[d] |= np.sum(np.where(mark, 1 << levels, 0), 1)
        now = (self.done_at[d] == 0) & (card >= self.p["threshold"])
        self.done_at[d[now]] = max(t, 1)
        self.pend_from[d] = -1

    def _pick(self, t, d):
        if not len(d):
            return
        p, L = self.p, self.L
        frm, lvl, rnk = self.q_from[d], self.q_lvl[d], self.q_rank[d]
        filled = frm >= 0
        rows = np.arange(len(d))[:, None]
        half = self.half[lvl]
        if self.exact:
            ver, agg = self.ver[d], self.agg[d]
            total = agg | ver
            inc_pc = self._per_level(total, d)
            agg_pc = self._per_level(agg, d)
            sig = self.q_sig[d]
            m = self.lmask[lvl, d[:, None]]
            inc_e = total[:, None] & m
            ver_e = ver[:, None] & m
            agg_e = agg[:, None] & m
            disjoint = ~(sig & inc_e).any(-1)
            merged = np.where(disjoint[..., None], sig | inc_e, sig)
            size_if = self._count(merged | ver_e)
            improving = filled & (size_if > inc_pc[rows, lvl])
            have = agg_pc[rows, lvl]
            score = np.where((sig & agg_e).any(-1),
                             np.maximum(0, self._count(sig | ver_e) - have),
                             have + self._count(sig))
            score = np.where(have >= half, 0, score)
        else:
            val = self.q_val[d]
            cur = self.best[d[:, None], lvl]
            improving = filled & (val > cur)
            score = np.where(cur >= half, 0, val - cur)
        low = np.full((len(d), L), BIG)
        for l in range(L):
            low[:, l] = np.where(filled & (lvl == l), rnk, BIG).min(1)
        inside = improving & (rnk <= low[rows, lvl] + self.window[d, None])
        has = np.zeros((len(d), L), bool)
        slot = np.zeros((len(d), L), np.int64)
        for l in range(1, L):
            sc = np.where(inside & (lvl == l), score, -1)
            in_ok = sc.max(1) > 0
            outside = improving & ~inside & (lvl == l)
            slot[:, l] = np.where(in_ok, sc.argmax(1), outside.argmax(1))
            has[:, l] = in_ok | outside.any(1)
        n_lv = has.sum(1)
        r = world.draw_below(world.mix3(self.seed, world.TAG_LEVEL, t), d,
                             n_lv)
        level = np.argmax(np.cumsum(has, 1) == (r + 1)[:, None], 1)
        do = n_lv > 0
        s = slot[np.arange(len(d)), level]
        dd = d[do]
        vfrom = frm[np.arange(len(d)), s][do]
        grown = np.clip(2 * self.window[dd], p["window_min"],
                        p["window_max"])
        self.window[dd] = np.minimum(grown,
                                     np.maximum(self.half[level[do]], 1))
        self.pend_from[dd] = vfrom
        self.pend_level[dd] = level[do]
        self.pend_at[dd] = t + p["pairing_time"]
        self.sigs_checked[dd] += 1
        if self.exact:
            self.demoted[dd, vfrom] = True
            self.pend_sig[dd] = sig[do, s[do]]
        else:
            self.pend_val[dd] = val[do, s[do]]
        # entries that cannot improve leave, and so does the one picked
        frm = np.where(improving, frm, -1)
        frm[np.flatnonzero(do), s[do]] = -1
        self.q_from[d] = frm

    def _send(self, t, active):
        p, n, L, K = self.p, self.n, self.L, self.K
        ids = self.ids
        done = self.done_at > 0
        period = p["dissemination_period_ms"]
        tick = active & ((t - 1) % period == 0)
        go = tick & (~done | (self.cycles > 0))
        self.cycles = np.where(tick & done, np.maximum(self.cycles - 1, 0),
                               self.cycles)
        if self.exact:
            total = self.agg | self.ver
            per = self._per_level(total)
            avoid = self._per_level(self.finished)
        else:
            per = self.best
        out = 1 + np.cumsum(per, 1) - per
        nw = 3 if self.exact else 2
        dest = np.full((n, K), -1, np.int64)
        words = np.zeros((n, K, nw), np.int64)
        size = np.ones((n, K), np.int64)
        rslot = (t // period) % (p["horizon"] // max(1, period) + 2)
        look = p["emission_lookahead"]
        for l in range(1, L):
            h = self.half[l]
            opened = (t >= (l - 1) * p["level_wait_time"]) | (out[:, l] >= h)
            if self.exact:
                others = avoid[:, l] < h
                cand = self.emission[ids[:, None],
                                     h + (self.pos[:, l, None] +
                                          np.arange(look)) % h]
                free = ~self._has(self.finished, ids[:, None], cand)
                found = free.any(1)
                first = free.argmax(1)
                peer = cand[ids, first]
                send = go & opened & others & found
                step = np.where(found, first + 1, look)
                moves = tick & opened & others
                words[:, l - 1, 1] = per[:, l] >= h
                words[:, l - 1, 2] = rslot
            else:
                peer = self._hashed_peer(ids, l, self.pos[:, l])
                send = go & opened
                step = 1
                moves = tick & opened
                words[:, l - 1, 1] = out[:, l]
            self.pos[:, l] = np.where(moves, (self.pos[:, l] + step) % h,
                                      self.pos[:, l])
            dest[:, l - 1] = np.where(send, peer, -1)
            words[:, l - 1, 0] = l
            size[:, l - 1] = 1 + h // 8 + 192
        fp = p["fast_path"]
        if fp > 0:
            low = self.fast & -self.fast
            fl = np.where(low > 0, np.log2(np.maximum(low, 1)).astype(
                np.int64), 0)
            fh = np.maximum(self.half[fl], 1)
            fpos = self.pos[ids, fl]
            offs = (fpos[:, None] + np.arange(fp)) % fh[:, None]
            fsend = (fl > 0) & active & ~done
            cols = slice(L - 1, L - 1 + fp)
            fl1 = np.maximum(fl, 1)
            if self.exact:
                cand = self.emission[ids[:, None],
                                     np.minimum(fh[:, None] + offs, n - 1)]
                free = ~self._has(self.finished, ids[:, None], cand)
                dest[:, cols] = np.where(fsend[:, None] & free, cand, -1)
                words[:, cols, 2] = rslot
                self.pos[ids, fl1] += np.where(fsend, free.sum(1), 0)
            else:
                cand = self._hashed_peer(ids[:, None], fl[:, None], offs)
                dest[:, cols] = np.where(fsend[:, None], cand, -1)
                words[:, cols, 1] = out[ids, fl][:, None]
                self.pos[ids, fl1] = np.where(
                    fsend, (self.pos[ids, fl1] + fp) % fh, self.pos[ids, fl1])
            words[:, cols, 0] = fl[:, None]
            size[:, cols] = (1 + fh // 8 + 192)[:, None]
            self.fast = np.where(fsend, self.fast & ~low, self.fast)
            self.fast = np.where(done, 0, self.fast)
        if self.exact:
            wrote = (dest >= 0).any(1)
            self.snap[wrote, rslot] = total[wrote]
        return dest, words, size

    def _hashed_peer(self, i, l, pos):
        """The pos-th receiver of node i at level l under the keyed
        emission order (cardinal mode: no stored lists)."""
        l = np.asarray(l)
        h = np.where(l > 0, 1 << np.maximum(l - 1, 0), 1)
        base = (i & ~(2 * h - 1)) + np.where(i & h, 0, h)
        key = world.mix3(world.mix(self.seed, world.TAG_EMIT), i, l)
        x = world.permute(key, np.where(pos < h, pos, 0),
                          np.maximum(l - 1, 0))
        return np.minimum(base + x, self.n - 1)


def trajectory(params: dict, seed: int, boundaries, tables=None) -> list:
    """The compared values of one seed at each ms of `boundaries`
    (ascending): per node done_at, messages and bytes sent and received,
    signatures checked and messages filtered; the clock; and the losses
    (dropped, clamped, evicted).  `tables`: `world.latency_tables` as
    evaluated where the cell runs (numpy's when not given)."""
    p = _params(params)
    n = p["node_count"]
    seed = int(np.int64(seed).astype(np.int32))
    if tables is None:
        tables = world.latency_tables(world.on_host)
    city = world.place_in_cities(seed, n)
    down = world.down_nodes(seed, n, p["nodes_down"])
    proto = Handel(p, seed, down)
    net = Network(n, seed, down, city, tables, p["horizon"], p["inbox_cap"],
                  proto.K, 3 if proto.exact else 2)
    out, t = [], 0
    for end in boundaries:
        while t < end:
            src, words, valid = net.deliver(t)
            dest, w, size = proto.step(t, src, words, valid)
            net.send(t, dest, w, size)
            t += 1
        out.append({"done_at": proto.done_at.copy(),
                    **{f: v.copy() for f, v in net.c.items()},
                    "sigs_checked": proto.sigs_checked.copy(),
                    "msg_filtered": proto.msg_filtered.copy(),
                    "time": t, "dropped": net.dropped,
                    "clamped": net.clamped, "evicted": proto.evicted,
                    "down": down})
    return out
