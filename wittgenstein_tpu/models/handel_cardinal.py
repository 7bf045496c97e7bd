"""Handel cardinal mode — the O(N*L) tier-3 state variant (SCALE.md).

Exact Handel state is Theta(N^2) bits: every [N, W] bitset row is N^2/8
bytes, ~0.8 TB of working set at 1M nodes (SCALE.md).  But Handel's OWN
accounting is per-level: each HLevel keeps ONE best aggregate for its
disjoint sibling range, and a node's total is the combination of per-level
bests plus its own signature (updateVerifiedSignatures,
protocols/Handel.java:686-750).  Within one level the ranges are disjoint
BY CONSTRUCTION, so tracking, per (node, level), only the best verified
CARDINALITY is faithful to the honest-path aggregation math:

  - state per node is ``lvl_best [N, L] int32`` — the count of the best
    verified aggregate per level (level l covers the 2^(l-1)-peer sibling
    range; the node's own signature is the implicit ``+1``);
  - a level-l message carries its sender's outgoing count
    ``1 + sum_{l' < l} lvl_best[l']`` (totalOutgoing = totalIncoming
    masked to the sender's block, Handel.java:725-735) computed AT SEND
    TIME directly into the payload — exact send-time aggregates with no
    snapshot pool at all;
  - the verification queue keeps ``q_cnt [N, Q]`` instead of
    ``q_sig [N, Q, W]``;
  - verifying an aggregate of count c at level l replaces the level best
    when c improves it (the reference's sizeIfIncluded > current gate,
    Handel.java:545-552,:710-724, under replace-not-union semantics).

What cardinal mode gives up (measured as drift vs exact mode in
``reports/CARDINAL_DRIFT.md``):

  - cross-entry set unions of PARTIALLY-overlapping same-level aggregates
    (real BLS cannot dedup overlapping aggregates either) and
    individual-signature repair of stale aggregates (ver_ind merge,
    Handel.java:700-724) — "best count wins" replaces both;
  - reception-rank demotion bits (Handel.java:830-834) — O(N^2) state;
    verified senders keep their original rank;
  - finishedPeers emission filtering (Handel.java:470-504) — the
    round-robin no longer skips peers that announced completion (the skip
    is a late-phase traffic optimization; completion flags are O(N^2) to
    remember);
  - byzantine attacks still work (the suicide plant is an invalid sig,
    the hidden plant a count-1 aggregate) but the per-node blacklist is
    an [N, W] bitset, so attack runs stay at tier-1/2 node counts; honest
    cardinal runs keep no O(N^2) state whatsoever.

Window scoring, rank windows, level scheduling, fast path, extraCycle,
desynchronized start, and the dissemination cadence port unchanged from
``models/handel.py`` — only the aggregate representation changed.  Ranks
and emission order come from the keyed permutations (hashed emission is
the only mode here: stored [N, N] lists are exactly what tier 3 removes).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..core import builders
from ..core import latency as latency_mod
from ..core.protocol import register
from ..core.state import EngineConfig, empty_outbox, init_net
from ..ops import bitset, prng
from ..ops.flat import gather2d, set2d
from ._levels import (LevelMixin, StaticScheduleMixin,
                      get_bit_rows as _get_bit_rows,
                      keyed_level_peer, merge_bounded_queue, sibling_base)
from .handel import TAG_BAD, TAG_EMIT, TAG_LEVEL, TAG_RANK, TAG_START

U32 = jnp.uint32
BIG = np.int32(1 << 30)


@struct.dataclass
class HandelCardinalState:
    seed: jnp.ndarray          # int32 scalar
    start_at: jnp.ndarray      # int32 [N] (desynchronizedStart, Handel:56-61)
    pairing: jnp.ndarray       # int32 [N] nodePairingTime (speedRatio-scaled)
    lvl_best: jnp.ndarray      # int32 [N, L] best verified count per level
    blacklist: jnp.ndarray     # u32 [N, W] (attacks only; [1, 1] otherwise)
    byz_seen: jnp.ndarray      # int32 [N, L] hidden-byz rank floor
    #                            ([1, 1] unless hidden_byzantine; see
    #                            _pick_verification)
    q_from: jnp.ndarray        # int32 [N, Q]  (-1 = empty slot)
    q_lvl: jnp.ndarray         # int32 [N, Q]
    q_rank: jnp.ndarray        # int32 [N, Q]
    q_cnt: jnp.ndarray         # int32 [N, Q] — the entry's aggregate count
    pos: jnp.ndarray           # int32 [N, L] — posInLevel round-robin pointer
    curr_window: jnp.ndarray   # int32 [N]
    added_cycle: jnp.ndarray   # int32 [N] extraCycle countdown
    pend_from: jnp.ndarray     # int32 [N] in-flight verification (-1 = none)
    pend_level: jnp.ndarray    # int32 [N]
    pend_bad: jnp.ndarray      # bool [N]
    pend_cnt: jnp.ndarray      # int32 [N]
    pend_at: jnp.ndarray       # int32 [N] — apply time
    fast_pending: jnp.ndarray  # int32 [N] — level bitmask of queued
    #                            fast-path sends (drained lowest-first)
    sigs_checked: jnp.ndarray  # int32 [N]
    msg_filtered: jnp.ndarray  # int32 [N]
    evicted: jnp.ndarray       # int32 scalar — queue evictions (diagnostic)


@register
class HandelCardinal(LevelMixin, StaticScheduleMixin):
    """O(N*L)-state Handel; construct directly or via Handel(mode="cardinal").

    Parameters mirror Handel.HandelParameters (Handel.java:22-142) minus the
    exact-mode scale switches (emission is always hashed, there is no
    snapshot pool)."""

    # Dests come from sibling-half level peer sets — never self
    # (core/network.unicast_floor_ms).
    may_self_send = False

    def __init__(self, node_count=2048, threshold=None, pairing_time=3,
                 level_wait_time=50, extra_cycle=10,
                 dissemination_period_ms=10, fast_path=10, nodes_down=0,
                 node_builder_name=None, network_latency_name=None,
                 desynchronized_start=0, window_initial=16, window_min=1,
                 window_max=128, queue_cap=16, inbox_cap=16, horizon=512,
                 byzantine_suicide=False, hidden_byzantine=False):
        if node_count & (node_count - 1):
            raise ValueError("we support only power-of-two node counts "
                             "(Handel.java:119-121)")
        threshold = (int(node_count * 0.99) if threshold is None
                     else threshold)
        if not (0 <= nodes_down < node_count and
                threshold + nodes_down <= node_count):
            raise ValueError(f"nodeCount={node_count}, threshold={threshold},"
                             f" nodesDown={nodes_down} (Handel.java:113-118)")
        self.node_count = node_count
        self.threshold = threshold
        self.pairing_time = pairing_time
        self.level_wait_time = level_wait_time
        self.extra_cycle = extra_cycle
        self.period = dissemination_period_ms
        self.fast_path = fast_path
        self.nodes_down = nodes_down
        self.desynchronized_start = desynchronized_start
        self.window_initial = window_initial
        self.window_min = window_min
        self.window_max = window_max
        self.queue_cap = queue_cap
        if (byzantine_suicide or hidden_byzantine) and not nodes_down:
            raise ValueError("byzantine attacks need nodes_down > 0 "
                             "(the attacker controls the down nodes)")
        self.byzantine_suicide = byzantine_suicide
        self.hidden_byzantine = hidden_byzantine
        self.attacks = byzantine_suicide or hidden_byzantine
        if self.attacks and node_count > 131072:
            raise ValueError(
                "byzantine attack runs keep an [N, W] blacklist bitset "
                "(O(N^2)); run attacks at tier-1/2 node counts")
        self.builder = builders.get_by_name(node_builder_name)
        self.latency = latency_mod.get_by_name(network_latency_name)

        # Queue-merge sort key: rank * (Q + S + 1) + pos, ranks < N (no
        # demotion in cardinal mode).
        s = inbox_cap + 1
        if node_count * (queue_cap + s + 1) >= 2 ** 31:
            raise ValueError(
                "queue-merge sort key would overflow int32: "
                f"{node_count}*({queue_cap}+{s}+1) >= 2**31; reduce "
                "queue_cap/inbox_cap")
        self.bits = max(1, int(math.log2(node_count)))
        self.levels = self.bits + 1            # levels 0..bits
        self.w = bitset.n_words(node_count) if self.attacks else 1
        # half[l] = size of the level-l peer range (0 for level 0).
        self.half = np.array([0] + [1 << (l - 1)
                                    for l in range(1, self.levels)],
                             np.int32)
        k = (self.levels - 1) + fast_path
        self.cfg = EngineConfig(n=node_count, horizon=horizon,
                                inbox_cap=inbox_cap, payload_words=2,
                                out_deg=k, bcast_slots=0)

    # ------------------------------------------------------------ primitives

    def _rank(self, seed, i_ids, s_ids):
        """Reception rank node i assigns to sender s (setReceivingRanks,
        Handel.java:940-948, as a keyed permutation; no demotion)."""
        key = prng.hash3(seed, TAG_RANK, i_ids)
        return prng.bij_perm(key, s_ids, self.bits)

    def _emission_peer(self, seed, i_ids, level, pos):
        """Hashed emission order (see models/handel.py; the only mode
        here)."""
        return jnp.minimum(
            keyed_level_peer(seed, TAG_EMIT, i_ids, level, pos),
            self.node_count - 1)

    def _byz_candidates(self, p, nodes, excl_bits, min_rank=None):
        """Per (node, level) lowest-reception-rank byzantine (down) peer
        (createSuicideByzantineSig Handel.java:538-559 /
        HiddenByzantine.firstByzantine :844-858).  Cardinal differences:
        no rank demotion, and exclusion is by blacklist bit plus an
        optional [N, L] rank floor (`min_rank`; only ranks strictly above
        it qualify) — the O(N*L) replacement for exact mode's
        already-aggregated-bit exclusion.  Only evaluated under attack
        flags."""
        n, L = self.node_count, self.levels
        ids = jnp.arange(n, dtype=jnp.int32)
        br = jnp.full((n, L), BIG, jnp.int32)
        bi = jnp.full((n, L), -1, jnp.int32)
        for l in range(1, L):
            half = 1 << (l - 1)
            base = sibling_base(ids, half)
            cand = base[:, None] + jnp.arange(half, dtype=jnp.int32)[None, :]
            rank = self._rank(p.seed, ids[:, None], cand)
            ok = nodes.down[cand] & ~_get_bit_rows(excl_bits, cand)
            if min_rank is not None:
                ok = ok & (rank > min_rank[:, l][:, None])
            rank = jnp.where(ok, rank, BIG)
            pos = jnp.argmin(rank, axis=1)
            best = jnp.take_along_axis(rank, pos[:, None], axis=1)[:, 0]
            bid = jnp.take_along_axis(cand, pos[:, None], axis=1)[:, 0]
            br = br.at[:, l].set(best)
            bi = bi.at[:, l].set(jnp.where(best < BIG, bid, -1))
        return br, bi

    # ---------------------------------------------------------------- init

    def init(self, seed):
        n, L, Q = self.node_count, self.levels, self.queue_cap
        seed = jnp.asarray(seed, jnp.int32)
        nodes = self.builder.build(seed, n)
        ids = jnp.arange(n, dtype=jnp.int32)

        if self.nodes_down:
            pri = prng.uniform_u32(prng.hash2(seed, TAG_BAD), ids)
            down = jnp.zeros((n,), bool).at[
                jnp.argsort(pri)[:self.nodes_down]].set(True)
            nodes = nodes.replace(down=down)

        start_at = (prng.uniform_int(prng.hash2(seed, TAG_START), ids,
                                     self.desynchronized_start)
                    if self.desynchronized_start else
                    jnp.zeros((n,), jnp.int32))
        pairing = jnp.maximum(
            1, (self.pairing_time * nodes.speed_ratio)).astype(jnp.int32)

        net = init_net(self.cfg, nodes, seed)
        pstate = HandelCardinalState(
            seed=seed, start_at=start_at, pairing=pairing,
            lvl_best=jnp.zeros((n, L), jnp.int32),
            blacklist=jnp.zeros((n, self.w) if self.attacks else (1, 1),
                                U32),
            byz_seen=jnp.full((n, L) if self.hidden_byzantine else (1, 1),
                              -1, jnp.int32),
            q_from=jnp.full((n, Q), -1, jnp.int32),
            q_lvl=jnp.zeros((n, Q), jnp.int32),
            q_rank=jnp.zeros((n, Q), jnp.int32),
            q_cnt=jnp.zeros((n, Q), jnp.int32),
            pos=jnp.zeros((n, L), jnp.int32),
            curr_window=jnp.full((n,), self.window_initial, jnp.int32),
            added_cycle=jnp.full((n,), self.extra_cycle, jnp.int32),
            pend_from=jnp.full((n,), -1, jnp.int32),
            pend_level=jnp.zeros((n,), jnp.int32),
            pend_bad=jnp.zeros((n,), bool),
            pend_cnt=jnp.zeros((n,), jnp.int32),
            pend_at=jnp.zeros((n,), jnp.int32),
            fast_pending=jnp.zeros((n,), jnp.int32),
            sigs_checked=jnp.zeros((n,), jnp.int32),
            msg_filtered=jnp.zeros((n,), jnp.int32),
            evicted=jnp.asarray(0, jnp.int32),
        )
        return net, pstate

    # ---------------------------------------------------------------- step

    def step(self, p: HandelCardinalState, nodes, inbox, t, key, hints=None):
        h = hints or {}
        active = (~nodes.down) & (t >= p.start_at + 1)
        # Sub-scopes of the engine's `protocol.step` stage (core/network).
        with jax.named_scope("handel.receive"):
            p = self._receive(p, nodes, inbox, t)
        if h.get("verify", True):
            with jax.named_scope("handel.apply"):
                p, nodes = self._apply_pending(p, nodes, t)
            with jax.named_scope("handel.verify"):
                p = self._pick_verification(p, nodes, t, active)
        with jax.named_scope("handel.disseminate"):
            p, out = self._disseminate(p, nodes, t, active,
                                       periodic=h.get("periodic", True))
        return p, nodes, out

    # -- receive: queue incoming counts (onNewSig, Handel.java:753-786)

    def _receive(self, p: HandelCardinalState, nodes, inbox, t):
        n, L, Q = self.node_count, self.levels, self.queue_cap
        ids = jnp.arange(n, dtype=jnp.int32)
        done = nodes.done_at > 0

        valid = inbox.valid                                   # [N, S]
        src = jnp.clip(inbox.src, 0, n - 1)
        level = jnp.clip(inbox.data[:, :, 0], 0, L - 1)
        halfs_arr = jnp.asarray(self.half)
        # The reference throws on size-overflowing aggregates
        # (HLevel.java:188-190); bounded shapes clip instead.
        cnt = jnp.clip(inbox.data[:, :, 1], 0, halfs_arr[level])

        # Filters (Handel.java:755-763): done -> counted; pre-start or
        # blacklisted sender -> silently ignored.
        if self.attacks:
            blk = _get_bit_rows(p.blacklist, src)
        else:
            blk = jnp.zeros_like(valid)
        ok = valid & ~done[:, None] & (t >= p.start_at)[:, None] & ~blk
        filtered = jnp.sum(valid & done[:, None], axis=1).astype(jnp.int32)

        rank_all = self._rank(p.seed, ids[:, None], src)

        # Bounded-queue merge (the shared policy of
        # _levels.merge_bounded_queue, minus the sig rows).
        sel2, _, ev = merge_bounded_queue(
            p.q_from, p.q_lvl, p.q_rank, src, level, rank_all, ok, Q,
            {"cnt": (p.q_cnt, cnt)}, {})

        return p.replace(q_from=sel2["from"], q_lvl=sel2["lvl"],
                         q_rank=sel2["rank"], q_cnt=sel2["cnt"],
                         msg_filtered=p.msg_filtered + filtered,
                         evicted=p.evicted + ev)

    # -- apply a finished verification (updateVerifiedSignatures, :686-750)

    def _apply_pending(self, p: HandelCardinalState, nodes, t):
        n, L = self.node_count, self.levels
        ids = jnp.arange(n, dtype=jnp.int32)
        due = (p.pend_from >= 0) & (t >= p.pend_at)

        # Bad sig -> blacklist the sender (suicide attack, :690-699).
        bad = due & p.pend_bad
        if self.attacks:
            blacklist = jnp.where(
                bad[:, None],
                p.blacklist | bitset.one_bit(jnp.maximum(p.pend_from, 0),
                                             self.w),
                p.blacklist)
        else:
            blacklist = p.blacklist
        ok = due & ~p.pend_bad

        # Best-count-wins replacement of the level aggregate (the
        # sizeIfIncluded > current improvement gate, :545-552,:710-724).
        cur = gather2d(p.lvl_best, ids, p.pend_level)
        improves = ok & (p.pend_cnt > cur)
        lvl_best = set2d(p.lvl_best, ids, p.pend_level, p.pend_cnt,
                         ok=improves)

        halfs = jnp.asarray(self.half)[None, :]               # [1, L]
        vs_half = jnp.where(p.pend_level > 0,
                            1 << jnp.clip(p.pend_level - 1, 0, 30), 0)
        just_completed = improves & (p.pend_cnt >= vs_half) & (vs_half > 0)

        # Fast path (:738-743): on level completion, queue every upper
        # level whose outgoing set is complete (drained one level per ms).
        fast_pending = p.fast_pending
        if self.fast_path > 0:
            og_size = 1 + jnp.cumsum(lvl_best, axis=1) - lvl_best
            og_complete = og_size >= halfs                     # [N, L]
            cand = (og_complete &
                    (jnp.arange(L)[None, :] > p.pend_level[:, None]) &
                    (halfs > 0) & just_completed[:, None])
            bits = jnp.sum(
                jnp.where(cand, jnp.int32(1) << jnp.arange(L)[None, :], 0),
                axis=1).astype(jnp.int32)
            fast_pending = fast_pending | bits

        # doneAt at threshold (:747-749); own signature is the +1.
        total_card = 1 + jnp.sum(lvl_best, axis=1)
        done_now = (nodes.done_at == 0) & ok & (total_card >= self.threshold)
        nodes = nodes.replace(done_at=jnp.where(
            done_now, jnp.maximum(t, 1), nodes.done_at).astype(jnp.int32))

        p = p.replace(blacklist=blacklist, lvl_best=lvl_best,
                      fast_pending=fast_pending,
                      pend_from=jnp.where(due, -1, p.pend_from))
        return p, nodes

    # -- pick next signature to verify (checkSigs/bestToVerify, :566-630)

    def _pick_verification(self, p: HandelCardinalState, nodes, t, active):
        n, L, Q = self.node_count, self.levels, self.queue_cap
        ids = jnp.arange(n, dtype=jnp.int32)
        due = (active & (p.pend_from < 0) &
               ((t - (p.start_at + 1)) % p.pairing == 0))

        halfs_arr = jnp.asarray(self.half)
        rows = ids[:, None]
        filled = p.q_from >= 0                                 # [N, Q]
        elvl = p.q_lvl
        cur = gather2d(p.lvl_best, rows, elvl)                 # [N, Q]
        half_e = halfs_arr[elvl]
        if self.attacks:
            blk = _get_bit_rows(p.blacklist, jnp.maximum(p.q_from, 0))
        else:
            blk = jnp.zeros_like(filled)

        # sizeIfIncluded (:545-552) under replace semantics: an entry
        # improves iff its count beats the current level best (counts are
        # capped at the level size, so complete levels never improve).
        improving = filled & ~blk & (p.q_cnt > cur)
        keep = improving | ~filled          # curation (:597-614)

        # windowIndex = min rank over the whole queue per level (:573-574).
        lvl_eq = (elvl[:, None, :] ==
                  jnp.arange(L, dtype=jnp.int32)[None, :, None])  # [N, L, Q]
        rank_b = jnp.where(filled[:, None, :] & lvl_eq, p.q_rank[:, None, :],
                           BIG)
        win_lo = jnp.min(rank_b, axis=2)                       # [N, L]
        win_lo_e = gather2d(win_lo, rows, elvl)
        inside = improving & (p.q_rank <= win_lo_e +
                              p.curr_window[:, None])

        # score (:651-664): replacement entries score their count delta
        # (the newTotal - existing branch; cardinal aggregates always
        # "interfere" — same level range, replace-not-union).
        score = jnp.where(cur >= half_e, 0, p.q_cnt - cur)
        score_in = jnp.where(inside, score, -1)

        # Per-level best: inside-window best score, else lowest rank outside.
        score_b = jnp.where(lvl_eq, score_in[:, None, :], -1)
        in_slot = jnp.argmax(score_b, axis=2)                  # [N, L]
        in_ok = jnp.max(score_b, axis=2) > 0
        out_rank_b = jnp.where(lvl_eq & (improving & ~inside)[:, None, :],
                               p.q_rank[:, None, :], BIG)
        out_slot = jnp.argmin(out_rank_b, axis=2)
        out_ok = jnp.min(out_rank_b, axis=2) < BIG
        best_slot = jnp.where(in_ok, in_slot, out_slot)        # [N, L]
        has_best = (in_ok | out_ok) & due[:, None]

        # byzantineSuicide (Handel.java:538-559,:577-583).
        if self.byzantine_suicide:
            sbr, sbi = self._byz_candidates(p, nodes, p.blacklist)
            s_ok = ((win_lo < BIG) &
                    (sbr < win_lo + p.curr_window[:, None]))   # [N, L]
            has_best = has_best | (s_ok & due[:, None])

        # chooseBestFromLevels (:788-790): uniform random non-empty level.
        cnt_lv = jnp.sum(has_best, axis=1).astype(jnp.int32)
        r = prng.uniform_int(prng.hash3(p.seed, TAG_LEVEL, t), ids,
                             jnp.maximum(cnt_lv, 1))
        csum = jnp.cumsum(has_best, axis=1).astype(jnp.int32)
        pick_level = jnp.argmax((csum == r[:, None] + 1) & has_best, axis=1)
        do = due & (cnt_lv > 0)

        slot = gather2d(best_slot, ids, pick_level)
        vfrom = gather2d(p.q_from, ids, slot)
        # Queue entries are never bad (only attack plants are, and those
        # go straight to pend): no q_bad column exists in cardinal mode.
        vbad = jnp.zeros_like(do)
        vcnt = gather2d(p.q_cnt, ids, slot)
        keep_entry = jnp.zeros_like(do)

        if self.byzantine_suicide:
            use_s = do & gather2d(s_ok, ids, pick_level)
            s_id = gather2d(sbi, ids, pick_level)
            vfrom = jnp.where(use_s, s_id, vfrom)
            vbad = vbad | use_s
            vcnt = jnp.where(use_s, 0, vcnt)
            keep_entry = keep_entry | use_s

        # HiddenByzantine (Handel.java:840-917): the plant is a count-1
        # aggregate; its exact-mode score is agg_card + 1 (a disjoint
        # single bit, :651-664) — kept as cur + 1 here.  Exact mode stops
        # re-attacks because a verified plant's bit joins the aggregate
        # (excluded by firstByzantine) and its sender is rank-demoted;
        # neither exists in cardinal state, so the [N, L] `byz_seen` rank
        # floor plays that role: each byzantine peer attacks a given
        # (node, level) at most once (a verified-or-planted peer is never
        # reused; exact mode can reuse one whose queue entry was evicted
        # unverified — a rare, strictly-weaker difference).
        byz_seen = p.byz_seen
        if self.hidden_byzantine:
            hbr, hbi = self._byz_candidates(p, nodes, p.blacklist,
                                            min_rank=p.byz_seen)
            h_rank = gather2d(hbr, ids, pick_level)
            h_id = gather2d(hbi, ids, pick_level)
            honest = do & ~keep_entry
            queued = jnp.any((p.q_from == h_id[:, None]) &
                             (p.q_lvl == pick_level[:, None]), axis=1)
            can = (honest & (h_id >= 0) & ~queued &
                   (h_rank < gather2d(p.q_rank, ids, slot)))   # :898-901
            h_score = gather2d(p.lvl_best, ids, pick_level) + 1
            s_picked = gather2d(score, ids, slot)
            was_in = gather2d(in_ok, ids, pick_level)
            h_win = can & (~was_in | (h_score > s_picked))
            vfrom = jnp.where(h_win, h_id, vfrom)
            vbad = vbad & ~h_win
            vcnt = jnp.where(h_win, 1, vcnt)
            keep_entry = keep_entry | h_win
            h_fail = can & ~h_win                               # :905-913
            byz_seen = set2d(byz_seen, ids, pick_level, h_rank, ok=can)

        # Window resize (:821-823).
        lsize = jnp.maximum(halfs_arr[pick_level], 1)
        grown = jnp.where(vbad, p.curr_window // 4, 2 * p.curr_window)
        new_win = jnp.clip(grown, self.window_min, self.window_max)
        curr_window = jnp.where(do, jnp.minimum(new_win, lsize),
                                p.curr_window)

        # Curation sweep for due nodes + removal of the picked entry.
        # (No rank demotion in cardinal mode — O(N^2) bits.)
        q_from = jnp.where(due[:, None] & ~keep, -1, p.q_from)
        q_from = set2d(q_from, ids, slot, -1, ok=do & ~keep_entry)
        q_lvl, q_rank, q_cnt = p.q_lvl, p.q_rank, p.q_cnt

        if self.hidden_byzantine:
            # A failed attack leaves the plant in the queue (:905-913).
            free = q_from < 0
            any_free = jnp.any(free, axis=1)
            worst = jnp.argmax(jnp.where(free, -1, q_rank), axis=1)
            worst_rank = jnp.take_along_axis(q_rank, worst[:, None],
                                             axis=1)[:, 0]
            islot = jnp.where(any_free, jnp.argmax(free, axis=1), worst)
            ins = h_fail & (any_free | (h_rank < worst_rank))
            q_from = set2d(q_from, ids, islot, h_id, ok=ins)
            q_lvl = set2d(q_lvl, ids, islot, pick_level, ok=ins)
            q_rank = set2d(q_rank, ids, islot, h_rank, ok=ins)
            q_cnt = set2d(q_cnt, ids, islot, 1, ok=ins)

        return p.replace(
            q_from=q_from, q_lvl=q_lvl, q_rank=q_rank, q_cnt=q_cnt,
            curr_window=curr_window, byz_seen=byz_seen,
            pend_from=jnp.where(do, vfrom, p.pend_from),
            pend_level=jnp.where(do, pick_level, p.pend_level),
            pend_bad=jnp.where(do, vbad, p.pend_bad),
            pend_cnt=jnp.where(do, vcnt, p.pend_cnt),
            pend_at=jnp.where(do, t + p.pairing, p.pend_at),
            sigs_checked=p.sigs_checked + do.astype(jnp.int32))

    # -- dissemination (doCycle, :331-343,:470-504) + outbox assembly

    def _disseminate(self, p: HandelCardinalState, nodes, t, active,
                     periodic=True):
        n, L = self.node_count, self.levels
        ids = jnp.arange(n, dtype=jnp.int32)
        done = nodes.done_at > 0
        halfs_np = self.half
        halfs = jnp.asarray(halfs_np)[None, :]
        og_size = 1 + jnp.cumsum(p.lvl_best, axis=1) - p.lvl_best  # [N, L]
        # Non-periodic ms can only populate the fast-path slots: narrow
        # outbox with preserved slot ids (Outbox.slot0) — see
        # models/handel.py._disseminate.  The outbox pieces are built by
        # CONSTRUCTION (stack/concatenate of broadcasts), never by slice
        # updates into a zero [N, K, 3] buffer: XLA materializes such
        # scatter operands with (8, 128)-tiled padding on the tiny
        # trailing dims — 12.8x expansion, 1.5 GB at 2^20 nodes
        # (observed in the r4 1M-run OOM dump).
        K = self.cfg.out_deg if periodic else max(1, self.fast_path)
        koff = L - 1 if periodic else 0

        # `periodic=False` (static phase hint, see core/network.scan_chunk):
        # no node can be on a period boundary, so the per-period block is
        # the identity (send_l all-False, pos/added_cycle unchanged) and
        # only the every-ms fast path below remains.
        if periodic:
            per_due = active & ((t - (p.start_at + 1)) % self.period == 0)
            send_ok = per_due & (~done | (p.added_cycle > 0))
            added_cycle = jnp.where(per_due & done,
                                    jnp.maximum(p.added_cycle - 1, 0),
                                    p.added_cycle)

            og_complete = og_size >= halfs
            lvl_idx = jnp.arange(L, dtype=jnp.int32)[None, :]
            is_open = ((t >= (lvl_idx - 1) * self.level_wait_time) |
                       og_complete) & (halfs > 0)

            # Round-robin through the keyed emission permutation.  No
            # finishedPeers/blacklist candidate filtering in cardinal mode
            # (O(N^2) bits; the skip is a traffic optimization, :470-504).
            peer = self._emission_peer(p.seed, ids[:, None], lvl_idx, p.pos)
            send_l = send_ok[:, None] & is_open
            adv = per_due[:, None] & is_open
            half_cols = jnp.maximum(halfs, 1)
            pos = jnp.where(adv, (p.pos + 1) % half_cols, p.pos)

            # SendSigs size (bytes): 1 + expected/8 + 96*2 (:255-259).
            sz_l = 1 + halfs // 8 + 192                        # [1, L]
            lvl_dest = jnp.where(send_l, peer, -1)[:, 1:]      # [N, L-1]
            # 2-word wire format (level, count): cardinal has no
            # finishedPeers tracking, so exact mode's levelFinished flag
            # word is dropped entirely — one fewer [H*N*C] mailbox plane
            # (2.1 GB at 2^20 nodes; the flag carried no information for
            # cardinal receivers).
            lvl_words = (jnp.broadcast_to(lvl_idx, (n, L))[:, 1:],
                         og_size[:, 1:])
            lvl_sizes = jnp.broadcast_to(sz_l, (n, L))[:, 1:]
        else:
            added_cycle = p.added_cycle
            pos = p.pos

        # Fast-path sends on level completion (:738-743).
        fast_pending = p.fast_pending
        if self.fast_path > 0:
            fp = self.fast_path
            lsb = fast_pending & -fast_pending
            fl = jnp.where(lsb > 0,
                           31 - jax.lax.clz(jnp.maximum(lsb, 1)), 0)
            fl = fl.astype(jnp.int32)                          # [N], 0 = none
            halfs_arr = jnp.asarray(halfs_np)
            fhalf = jnp.maximum(halfs_arr[fl], 1)
            fpos = gather2d(pos, ids, fl)
            foffs = (fpos[:, None] + jnp.arange(fp)[None, :]) % \
                fhalf[:, None]
            fids = self._emission_peer(p.seed, ids[:, None],
                                       fl[:, None], foffs)
            fsend = (fl > 0) & active & ~done
            fast_dest = jnp.where(fsend[:, None], fids, -1)
            fcnt = gather2d(og_size, ids, fl)
            fast_words = (jnp.broadcast_to(fl[:, None], (n, fp)),
                          jnp.broadcast_to(fcnt[:, None], (n, fp)))
            fast_sizes = jnp.broadcast_to((1 + fhalf // 8 + 192)[:, None],
                                          (n, fp))
            pos = set2d(pos, ids, jnp.maximum(fl, 1),
                        (gather2d(pos, ids, jnp.maximum(fl, 1)) + fp) %
                        jnp.maximum(fhalf, 1), ok=fsend)
            fast_pending = jnp.where(fsend, fast_pending & ~lsb,
                                     fast_pending)
            fast_pending = jnp.where(done, 0, fast_pending)
        else:
            # No fast path: zero extra columns on a periodic ms, one
            # always-empty column otherwise (K = max(1, fast_path)).
            fcols = 0 if periodic else 1
            fast_dest = jnp.full((n, fcols), -1, jnp.int32)
            fast_words = tuple(jnp.zeros((n, fcols), jnp.int32)
                               for _ in range(2))
            fast_sizes = jnp.ones((n, fcols), jnp.int32)

        if periodic:
            dest = jnp.concatenate([lvl_dest, fast_dest], axis=1)
            payload = jnp.stack(
                [jnp.concatenate([lw, fw], axis=1)
                 for lw, fw in zip(lvl_words, fast_words)], axis=-1)
            sizes = jnp.concatenate([lvl_sizes, fast_sizes], axis=1)
        else:
            dest = fast_dest
            payload = jnp.stack(list(fast_words), axis=-1)
            sizes = fast_sizes
        assert dest.shape[1] == K, (dest.shape, K)

        # slot0 clamped into [0, out_deg) — see models/handel.py (the
        # fast_path == 0 narrow-outbox slot-id collision, ADVICE r3).
        out = empty_outbox(self.cfg, k=K,
                           slot0=0 if periodic else
                           min(L - 1, self.cfg.out_deg - 1)).replace(
            dest=dest, payload=payload, size=sizes)
        return p.replace(pos=pos, added_cycle=added_cycle,
                         fast_pending=fast_pending), out

    # ---------------------------------------------------------------- misc

    def done(self, pstate, nodes):
        return jnp.all(nodes.down | (nodes.done_at > 0))
