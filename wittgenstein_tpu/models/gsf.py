"""GSFSignature — "Gossiping San Fermín" BLS aggregation.

Reference: protocols/GSFSignature.java (769 lines).  Mechanism (SURVEY.md
§2.4): every node runs log2(N) San Fermín levels; each `periodDurationMs` it
walks its levels and sends, per open level, its best *finished-level prefix*
plus everything verified below that level to one round-robin peer
(doCycle, GSFSignature.java:212-224).  Incoming signature sets queue for
verification; every `pairingTime` ms the best-scoring set is verified
(evaluateSig/checkSigs, :482-534,:539-580).  Oversized sets complete several
levels at once (updateVerifiedSignatures, :383-460); completing a level
triggers `acceleratedCallsCount` immediate sends at the next levels
(:438-451).  Individual signatures ride along for byzantine resistance
(onNewSig, :546-553).

TPU-native design (mirrors models/handel.py; one [N, W] row per bitset):

* Levels share Handel's id-space geometry (allSigsAtLevel,
  GSFSignature.java:359-372 == Handel.java:667-680), so the LevelMixin
  popcount/range machinery applies unchanged.
* The global verified set V is ONE [N, W] row (own bit at init,
  GSFNode ctor :176).  A level's verified set is V & range_l; the replace
  update `andNot(waitedSigs); or(sigs)` (:432-436) is a masked merge on V.
  (The reference's per-level sets can briefly hold out-of-range stragglers;
  we fold those into V directly — statistical equivalence, SURVEY §7.4.3.)
* A message carries (level, finishedPrefix, roundSlot) — the actual sig set
  is reconstructed at delivery from the sender's snapshot pool:
  sigs = (pool[src, slot] & block(src, level-1)) | block(src, fin), which is
  exactly doCycle's `toSend` (getLastFinishedLevel :197-210 is the 2^fin
  block around the sender; the or-accumulated lower-level sets are
  pool & block(src, level-1)).
* toVerify (:539-553) is a fixed [N, Q] queue keyed by (from, level);
  newer sets from the same (from, level) replace older (supersets in
  practice); individual signatures enqueue once ever per (sender, level)
  via the got_indiv dedup row (:546-553).  checkSigs' score is evaluated
  for the whole queue in one shot; the winner verifies after
  `nodePairingTime` ms (pend_* slot), losers with score 0 are evicted —
  the reference's iterator-remove curation (:560-567).
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
from ..ops.flat import gather2d, gather_rows, set2d, set_rows
from ._levels import (LevelMixin, get_bit_rows as _get_bit_rows,
                      keyed_level_peer, select_queue)

TAG_BAD = 0x47424144      # bad-node choice
TAG_PERM = 0x47504552     # per-(node, level) peer-order permutation
U32 = jnp.uint32
BIG = np.int32(1 << 30)


@struct.dataclass
class GSFState:
    seed: jnp.ndarray          # int32 scalar
    pairing: jnp.ndarray       # int32 [N] nodePairingTime (speedRatio-scaled)
    verified: jnp.ndarray      # u32 [N, W] — global verified set V (:170)
    ver_indiv: jnp.ndarray     # u32 [N, W] indivVerifiedSig, all levels packed
    got_indiv: jnp.ndarray     # u32 [N, W] individualSignatures dedup (:551)
    remaining: jnp.ndarray     # int32 [N, L] remainingCalls per level
    pos: jnp.ndarray           # int32 [N, L] posInLevel round-robin pointer
    q_from: jnp.ndarray        # int32 [N, Q] (-1 = empty)
    q_lvl: jnp.ndarray         # int32 [N, Q]
    q_indiv: jnp.ndarray       # bool [N, Q]
    q_sig: jnp.ndarray         # u32 [N, Q, W] — the full queued set
    pend_from: jnp.ndarray     # int32 [N] in-flight verification (-1 = none)
    pend_lvl: jnp.ndarray      # int32 [N]
    pend_sig: jnp.ndarray      # u32 [N, W]
    pend_at: jnp.ndarray       # int32 [N]
    accel_pending: jnp.ndarray  # int32 [N] — bitmask of accelerated levels
    pool: jnp.ndarray          # u32 [N, R, W] — V snapshots per send round
    sigs_checked: jnp.ndarray  # int32 [N]
    evicted: jnp.ndarray       # int32 scalar


@register
class GSFSignature(LevelMixin):
    """Parameters mirror GSFSignatureParameters (GSFSignature.java:27-107)."""

    # Dests come from sibling-half level peer sets — never self
    # (core/network.unicast_floor_ms).
    may_self_send = False

    def __init__(self, node_count=1024, threshold=None, pairing_time=3,
                 timeout_per_level_ms=50, period_duration_ms=10,
                 accelerated_calls_count=10, nodes_down=0,
                 node_builder_name=None, network_latency_name=None,
                 queue_cap=16, inbox_cap=16, horizon=512,
                 pallas_merge=None):
        # Fused Pallas queue merge (ops/pallas_gsf_merge.py) —
        # bit-identical to the XLA merge (tests/test_gsf.py); shared
        # auto-default policy with Handel.
        from ..ops.pallas_merge import resolve_pallas_default
        self.pallas_merge = resolve_pallas_default(pallas_merge)
        if self.pallas_merge and queue_cap + 2 * inbox_cap > 255:
            # The kernel's unique-key headroom (BIG0 + position); fail
            # at construction, not after a 10-minute backend init.
            raise ValueError(
                f"pallas_merge supports queue_cap + 2*inbox_cap <= 255 "
                f"(got {queue_cap} + 2*{inbox_cap}); pass "
                "pallas_merge=False for wider rows")
        if node_count & (node_count - 1):
            raise ValueError("power-of-two node counts only (the reference "
                             "rounds to pow2, MoreMath.roundPow2)")
        threshold = (int(node_count * 0.99) if threshold is None
                     else threshold)
        if not (0 <= nodes_down < node_count and
                threshold + nodes_down <= node_count and
                threshold <= node_count):
            raise ValueError(f"nodeCount={node_count}, threshold={threshold},"
                             f" nodesDown={nodes_down} "
                             "(GSFSignature.java:70-75)")
        self.node_count = node_count
        self.threshold = threshold
        self.pairing_time = pairing_time
        self.timeout_per_level = timeout_per_level_ms
        self.period = period_duration_ms
        self.accel = accelerated_calls_count
        self.nodes_down = nodes_down
        self.queue_cap = queue_cap
        self.builder = builders.get_by_name(node_builder_name)
        self.latency = latency_mod.get_by_name(network_latency_name)

        self.bits = max(1, int(math.log2(node_count)))
        self.levels = self.bits + 1
        # The queue-merge sort key is (tier*(L+1)+lvl)*M + pos in int32
        # with tier <= 2 and M = Q + 2S (see receive); enforce it fits.
        _m = queue_cap + 2 * inbox_cap
        if (2 * (self.levels + 1) + self.levels) * _m + _m >= 2 ** 31:
            raise ValueError(
                "queue-merge sort key would overflow int32: reduce "
                f"queue_cap={queue_cap}/inbox_cap={inbox_cap}")
        self.w = bitset.n_words(node_count)
        self.rounds = horizon // max(1, period_duration_ms) + 2
        self.half = np.array([0] + [1 << (l - 1)
                                    for l in range(1, self.levels)],
                             np.int32)
        k = (self.levels - 1) + self.accel
        self.cfg = EngineConfig(n=node_count, horizon=horizon,
                                inbox_cap=inbox_cap, payload_words=3,
                                out_deg=k, bcast_slots=0)

    # ------------------------------------------------------------ primitives

    def _peer_at(self, seed, ids, level, pos):
        """The `pos`-th peer of `ids` at `level` in its shuffled peer order
        (randomSubset + Collections.shuffle, GSFSignature.java:462-476, as a
        keyed permutation of the level range — no stored [N, N] lists)."""
        return keyed_level_peer(seed, TAG_PERM, ids, level, pos)

    def _fin_level(self, pc):
        """Last finished level f: levels 1..f all complete (getLastFinished
        Level, :197-210).  pc [N, L] per-level popcounts of V."""
        halfs = jnp.asarray(self.half)[None, :]
        comp = (pc >= halfs) | (halfs == 0)          # level 0 always complete
        run = jnp.cumprod(comp.astype(jnp.int32), axis=1)
        return jnp.sum(run, axis=1).astype(jnp.int32) - 1   # [N], 0..L-1

    # ---------------------------------------------------------------- init

    def init(self, seed):
        n, w, L, Q = self.node_count, self.w, self.levels, self.queue_cap
        seed = jnp.asarray(seed, jnp.int32)
        nodes = self.builder.build(seed, n)
        ids = jnp.arange(n, dtype=jnp.int32)

        if self.nodes_down:
            pri = prng.uniform_u32(prng.hash2(seed, TAG_BAD), ids)
            down = jnp.zeros((n,), bool).at[
                jnp.argsort(pri)[:self.nodes_down]].set(True)
            nodes = nodes.replace(down=down)

        pairing = jnp.maximum(
            1, (self.pairing_time * nodes.speed_ratio)).astype(jnp.int32)
        halfs = jnp.asarray(self.half)
        remaining = jnp.broadcast_to(halfs[None, :], (n, L)).astype(jnp.int32)

        net = init_net(self.cfg, nodes, seed)
        pstate = GSFState(
            seed=seed, pairing=pairing,
            verified=bitset.one_bit(ids, w),
            ver_indiv=jnp.zeros((n, w), U32),
            got_indiv=jnp.zeros((n, w), U32),
            remaining=remaining,
            pos=jnp.zeros((n, L), jnp.int32),
            q_from=jnp.full((n, Q), -1, jnp.int32),
            q_lvl=jnp.zeros((n, Q), jnp.int32),
            q_indiv=jnp.zeros((n, Q), bool),
            q_sig=jnp.zeros((n, Q, w), U32),
            pend_from=jnp.full((n,), -1, jnp.int32),
            pend_lvl=jnp.zeros((n,), jnp.int32),
            pend_sig=jnp.zeros((n, w), U32),
            pend_at=jnp.zeros((n,), jnp.int32),
            accel_pending=jnp.zeros((n,), jnp.int32),
            pool=jnp.zeros((n, self.rounds, w), U32),
            sigs_checked=jnp.zeros((n,), jnp.int32),
            evicted=jnp.asarray(0, jnp.int32),
        )
        return net, pstate

    # ---------------------------------------------------------------- step

    def step(self, p: GSFState, nodes, inbox, t, key):
        onehot = self._word_onehot(jnp.arange(self.node_count,
                                              dtype=jnp.int32))
        subm = self._subword_masks(jnp.arange(self.node_count,
                                              dtype=jnp.int32))
        hi = jnp.arange(self.node_count, dtype=jnp.int32) >> 5

        p = self._receive(p, nodes, inbox, t)
        p, nodes = self._apply_pending(p, nodes, t, onehot, subm, hi)
        p = self._pick_verification(p, nodes, t, onehot, subm, hi)
        p, out = self._disseminate(p, nodes, t, onehot, subm, hi)
        return p, nodes, out

    # -- receive (onNewSig, :539-553)

    def _receive(self, p: GSFState, nodes, inbox, t):
        n, w, L, Q = self.node_count, self.w, self.levels, self.queue_cap
        S = inbox.src.shape[1]

        valid = inbox.valid
        src = jnp.clip(inbox.src, 0, n - 1)
        level = jnp.clip(inbox.data[:, :, 0], 0, L - 1)
        fin = jnp.clip(inbox.data[:, :, 1], 0, L - 1)
        rslot = jnp.clip(inbox.data[:, :, 2], 0, self.rounds - 1)

        # Reconstruct the sender's toSend set (see module docstring).
        pool_row = gather_rows(p.pool, src, rslot)            # [N, S, W]
        low = self._sender_block_mask(src, level)             # [N, S, W]
        fin_block = self._block_mask_dyn(src, fin)
        sig_all = (pool_row & low) | fin_block

        # Queue merge, vectorized across ALL slots at once (the unrolled
        # per-slot loop compiled S insert/evict blocks).  Bounded-queue
        # policy: queued INDIVIDUAL entries are immovable (their got_indiv
        # dedup bit would otherwise lose the sig forever — the reference
        # keys individuals per level, but a sender only ever appears at ONE
        # level of a given receiver); aggregates keep one entry per
        # (sender, level) — newest wins — prioritized by LEVEL ascending
        # (scoring favors early levels), existing before incoming, then
        # inbox-slot order.  Policy change from the old loop: ALL same-ms
        # aggregates now outrank same-ms individual sigs for capacity (the
        # loop interleaved them by slot); individuals fill leftover slots.
        # One tiered sort over (existing ∪ inc-agg ∪ inc-indiv) does it.
        M = Q + 2 * S
        later = jnp.triu(jnp.ones((S, S), bool), k=1)[None]
        dup = jnp.any((src[:, :, None] == src[:, None, :]) &
                      (level[:, :, None] == level[:, None, :]) &
                      valid[:, None, :] & later, axis=2)
        agg_ok = valid & ~dup                # newest same-key message wins
        superseded = jnp.any(
            (p.q_from[:, :, None] == src[:, None, :]) &
            (p.q_lvl[:, :, None] == level[:, None, :]) &
            (~p.q_indiv)[:, :, None] & agg_ok[:, None, :], axis=2)
        ex_keep = (p.q_from >= 0) & ~superseded

        # Incoming individuals: once ever per sender — first slot this ms
        # wins, and senders already in got_indiv are consumed.
        earlier = jnp.tril(jnp.ones((S, S), bool), k=-1)[None]
        dup_ind = jnp.any((src[:, :, None] == src[:, None, :]) &
                          valid[:, None, :] & earlier, axis=2)
        ind_ok = valid & ~dup_ind & ~_get_bit_rows(p.got_indiv, src)

        if self.pallas_merge:
            from ..ops.pallas_gsf_merge import gsf_merge_pallas
            q_from, q_lvl, q_indiv, q_sig, got_add, kept_ex_agg = \
                gsf_merge_pallas(
                    p.q_from, p.q_lvl, p.q_indiv, ex_keep, p.q_sig,
                    src, level, agg_ok, ind_ok, sig_all, levels=L,
                    interpret=jax.default_backend() != "tpu")
            got_indiv = p.got_indiv | got_add
            evicted = p.evicted + jnp.sum(
                jnp.sum(ex_keep & ~p.q_indiv, axis=1) -
                kept_ex_agg).astype(jnp.int32)
            return p.replace(q_from=q_from, q_lvl=q_lvl,
                             q_indiv=q_indiv, q_sig=q_sig,
                             got_indiv=got_indiv, evicted=evicted)

        u_from = jnp.concatenate(
            [jnp.where(ex_keep, p.q_from, -1),
             jnp.where(agg_ok, src, -1),
             jnp.where(ind_ok, src, -1)], axis=1)           # [N, M]
        u_lvl = jnp.concatenate([p.q_lvl, level, level], axis=1)
        u_indiv = jnp.concatenate(
            [p.q_indiv, jnp.zeros_like(agg_ok),
             jnp.ones_like(ind_ok)], axis=1)
        u_sig = jnp.concatenate(
            [p.q_sig, sig_all,
             jnp.where(ind_ok[..., None], bitset.one_bit(src, w),
                       U32(0))], axis=1)                     # [N, M, W]

        valid_u = u_from >= 0
        pos = jnp.arange(M, dtype=jnp.int32)[None, :]
        is_inc_ind = pos >= Q + S                            # tier 2
        tier = jnp.where(is_inc_ind, 2,
                         jnp.where(u_indiv, 0, 1))           # existing
        #                                                      indiv = 0
        lvl_term = jnp.where(tier == 1, u_lvl, 0)
        sel2, sel3, order = select_queue(
            (tier * (L + 1) + lvl_term) * M + pos, valid_u, Q,
            {"from": u_from, "lvl": u_lvl, "indiv": u_indiv},
            {"sig": u_sig})
        q_from, q_lvl, q_indiv = sel2["from"], sel2["lvl"], sel2["indiv"]
        q_sig = sel3["sig"]

        # got_indiv consumed only for incoming individuals that made it in.
        sel_new_ind = (jnp.take_along_axis(
            jnp.broadcast_to(is_inc_ind, valid_u.shape), order, axis=1) &
            (q_from >= 0))
        ind_bits = jnp.where(sel_new_ind[..., None],
                             bitset.one_bit(jnp.maximum(q_from, 0), w),
                             U32(0))
        got_indiv = p.got_indiv | jax.lax.reduce(
            ind_bits, U32(0), jax.lax.bitwise_or, (1,))

        # Diagnostic: displaced existing aggregate entries.
        kept_ex_agg = jnp.sum(
            (order < Q) &
            jnp.take_along_axis(valid_u & ~u_indiv, order, axis=1), axis=1)
        evicted = p.evicted + jnp.sum(
            jnp.sum(ex_keep & ~p.q_indiv, axis=1) -
            kept_ex_agg).astype(jnp.int32)

        return p.replace(q_from=q_from, q_lvl=q_lvl, q_indiv=q_indiv,
                         q_sig=q_sig, got_indiv=got_indiv, evicted=evicted)

    # -- apply a finished verification (updateVerifiedSignatures, :383-460)

    def _apply_pending(self, p: GSFState, nodes, t, onehot, subm, hi):
        n, w, L = self.node_count, self.w, self.levels
        ids = jnp.arange(n, dtype=jnp.int32)
        halfs = jnp.asarray(self.half)
        due = (p.pend_from >= 0) & (t >= p.pend_at)

        lvl = p.pend_lvl
        sigs = p.pend_sig
        exp = halfs[lvl]                                      # [N]

        # Individual sig marking (:387-390): |sigs| == 1 marks the sender
        # as individually verified; every apply or's the level's verified
        # individual sigs into the set.
        card0 = bitset.popcount(sigs)
        mark_ind = due & (card0 == 1)
        ver_indiv = jnp.where(mark_ind[:, None], p.ver_indiv | sigs,
                              p.ver_indiv)
        lmask = self._range_mask_dyn(ids, lvl)                # [N, W]
        sigs = sigs | (ver_indiv & lmask)

        # Oversized set -> complete the consecutive levels it includes
        # (:395-417), then clamp to the level range.
        pc_v = self._level_pc(p.verified, onehot, subm, hi)   # [N, L]
        oversized = due & (bitset.popcount(sigs) > exp)
        incl = jnp.stack(
            [jnp.ones((n,), bool)] +
            [bitset.includes(sigs & self._range_mask_dyn(
                ids, jnp.full((n,), l, jnp.int32)),
                self._range_mask_dyn(ids, jnp.full((n,), l, jnp.int32)))
             for l in range(1, L)], axis=1)                   # [N, L]
        run = jnp.cumprod(incl.astype(jnp.int32), axis=1)
        fin_in = jnp.sum(run, axis=1).astype(jnp.int32) - 1   # consec prefix
        was_comp = pc_v >= halfs[None, :]
        lvl_idx = jnp.arange(L, dtype=jnp.int32)[None, :]
        newly = (run > 0) & ~was_comp & (lvl_idx >= 1) & oversized[:, None]
        reset_any = jnp.any(newly, axis=1)
        comp_mask = self._block_mask_dyn(ids, jnp.where(oversized, fin_in, 0))
        verified = jnp.where(oversized[:, None], p.verified | comp_mask,
                             p.verified)
        sigs = jnp.where(oversized[:, None], lmask, sigs)

        # Merge with the level's current set when disjoint (:419-425).
        ver_l = verified & lmask
        ver_l_card = bitset.popcount(ver_l)
        disjoint = ~bitset.intersects(sigs, ver_l) & (ver_l_card > 0)
        sigs = jnp.where(disjoint[:, None], sigs | ver_l, sigs)

        # Improvement -> replace the level's set inside V; out-of-range bits
        # fold into V directly (:427-436).
        improved = due & ((bitset.popcount(sigs & lmask) > ver_l_card) |
                          reset_any)
        verified = jnp.where(improved[:, None],
                             (verified & ~lmask) | sigs, verified)

        # Reset remainingCalls for levels >= min(affected) (:423-430 + the
        # newly-completed reset); affected base = the applied level, or the
        # first newly completed level if lower.
        first_new = jnp.argmax(newly, axis=1).astype(jnp.int32)
        base_l = jnp.where(reset_any, jnp.minimum(lvl, first_new), lvl)
        reset_row = improved[:, None] & (lvl_idx >= base_l[:, None])
        remaining = jnp.where(reset_row, halfs[None, :], p.remaining)

        # Accelerated calls (:438-451): queue levels (lvl+1 .. fin+1).
        accel_pending = p.accel_pending
        if self.accel > 0:
            pc2 = self._level_pc(verified, onehot, subm, hi)
            fin_now = self._fin_level(pc2)                     # [N]
            cand = (improved[:, None] & (lvl_idx > lvl[:, None]) &
                    (lvl_idx <= jnp.minimum(fin_now + 1, L - 1)[:, None]))
            bits_ = jnp.sum(jnp.where(cand, jnp.int32(1) << lvl_idx, 0),
                            axis=1).astype(jnp.int32)
            accel_pending = accel_pending | bits_

        # doneAt at threshold (:452-456).
        total = bitset.popcount(verified)
        done_now = (nodes.done_at == 0) & due & (total >= self.threshold)
        nodes = nodes.replace(done_at=jnp.where(
            done_now, jnp.maximum(t, 1), nodes.done_at).astype(jnp.int32))

        p = p.replace(verified=verified, ver_indiv=ver_indiv,
                      remaining=remaining, accel_pending=accel_pending,
                      pend_from=jnp.where(due, -1, p.pend_from))
        return p, nodes

    # -- checkSigs / evaluateSig (:482-580)

    def _pick_verification(self, p: GSFState, nodes, t, onehot, subm, hi):
        n, L, Q = self.node_count, self.levels, self.queue_cap
        ids = jnp.arange(n, dtype=jnp.int32)
        halfs = jnp.asarray(self.half)
        active = ~nodes.down
        due = active & (p.pend_from < 0) & ((t - 1) % p.pairing == 0) & \
            (t >= 1)

        filled = p.q_from >= 0                                 # [N, Q]
        rows = ids[:, None]
        elvl = p.q_lvl
        sig = p.q_sig
        exp = halfs[elvl]                                      # [N, Q]
        if self.pallas_merge:
            # Same switch as the merge kernel: one fused pass instead
            # of ~5 HBM round-trips over the sig plane
            # (ops/pallas_score.gsf_score_pallas, bit-equal by test).
            from ..ops.pallas_score import gsf_score_pallas
            (ver_l_card, card_sig, inter, pc_wi, pc_wv,
             inter_ind) = gsf_score_pallas(
                sig, elvl, ids, p.verified, p.ver_indiv,
                interpret=jax.default_backend() != "tpu")
        else:
            emask = self._range_mask_dyn(rows, elvl)           # [N, Q, W]
            ver_l = p.verified[:, None, :] & emask
            ver_l_card = bitset.popcount(ver_l)
            indiv_l = p.ver_indiv[:, None, :] & emask
            with_indiv = indiv_l | sig
            card_sig = bitset.popcount(sig)
            inter = bitset.intersects(sig, ver_l)
            pc_wi = bitset.popcount(with_indiv)
            pc_wv = bitset.popcount(with_indiv | ver_l)
            inter_ind = bitset.intersects(sig, indiv_l)

        new_total = jnp.where(
            ver_l_card == 0, card_sig,
            jnp.where(inter, pc_wi, pc_wv))
        added = jnp.where(ver_l_card == 0, new_total,
                          new_total - ver_l_card)
        indiv_bonus = ((card_sig == 1) & ~inter_ind).astype(jnp.int32)
        score = jnp.where(
            added <= 0, indiv_bonus,
            jnp.where(new_total == exp, 1_000_000 - elvl * 10,
                      100_000 - elvl * 100 + added))
        score = jnp.where(ver_l_card >= exp, 0, score)
        score = jnp.where(filled, score, -1)

        best = jnp.argmax(score, axis=1)                       # [N]
        best_score = jnp.take_along_axis(score, best[:, None], axis=1)[:, 0]
        do = due & (best_score > 0)

        vfrom = gather2d(p.q_from, ids, best)
        vlvl = gather2d(p.q_lvl, ids, best)
        vsig = gather_rows(p.q_sig, ids, best)

        # Curation: due nodes drop score-0 entries (:560-567) + the winner.
        drop = due[:, None] & (score == 0)
        q_from = jnp.where(drop, -1, p.q_from)
        q_from = set2d(q_from, ids, best, -1, ok=do)

        return p.replace(
            q_from=q_from,
            pend_from=jnp.where(do, vfrom, p.pend_from),
            pend_lvl=jnp.where(do, vlvl, p.pend_lvl),
            pend_sig=jnp.where(do[:, None], vsig, p.pend_sig),
            pend_at=jnp.where(do, t + p.pairing, p.pend_at),
            sigs_checked=p.sigs_checked + do.astype(jnp.int32))

    # -- doCycle + accelerated sends + outbox (:191-224, :438-451)

    def _disseminate(self, p: GSFState, nodes, t, onehot, subm, hi):
        n, w, L = self.node_count, self.w, self.levels
        ids = jnp.arange(n, dtype=jnp.int32)
        halfs_np = self.half
        halfs = jnp.asarray(halfs_np)
        active = ~nodes.down
        per_due = active & (t >= 1) & ((t - 1) % self.period == 0)

        pc = self._level_pc(p.verified, onehot, subm, hi)      # [N, L]
        fin = self._fin_level(pc)                              # [N]
        # card(V & block_{l-1}) = 1 + sum_{l'<l} pc  (own bit + lower ranges).
        cum_low = 1 + jnp.cumsum(pc, axis=1) - pc              # [N, L]
        lvl_idx = jnp.arange(L, dtype=jnp.int32)[None, :]
        two_fin = (jnp.int32(1) << jnp.clip(fin, 0, 30))[:, None]
        to_send_card = jnp.where(fin[:, None] <= lvl_idx - 1, cum_low,
                                 two_fin)

        # hasStarted (:283-303): timeout or a full set to send.
        started = ((t >= lvl_idx * self.timeout_per_level) |
                   (to_send_card >= halfs[None, :])) & (halfs[None, :] > 0)
        send_l = per_due[:, None] & started & (p.remaining > 0)

        peer = self._peer_at(p.seed, ids[:, None],
                             jnp.broadcast_to(lvl_idx, (n, L)),
                             p.pos % jnp.maximum(halfs[None, :], 1))
        pos = jnp.where(send_l, (p.pos + 1) % jnp.maximum(halfs[None, :], 1),
                        p.pos)
        remaining = jnp.where(send_l, p.remaining - 1, p.remaining)

        rslot = (t // self.period) % self.rounds
        K = self.cfg.out_deg
        dest = jnp.full((n, K), -1, jnp.int32)
        payload = jnp.zeros((n, K, 3), jnp.int32)
        sizes = jnp.ones((n, K), jnp.int32)
        # SendSigs size = 1 + expected/8 + 96 (:146-152).
        sz_l = 1 + halfs[None, :] // 8 + 96
        dest = dest.at[:, :L - 1].set(jnp.where(send_l, peer, -1)[:, 1:])
        payload = payload.at[:, :L - 1, 0].set(
            jnp.broadcast_to(lvl_idx, (n, L))[:, 1:])
        payload = payload.at[:, :L - 1, 1].set(
            jnp.broadcast_to(fin[:, None], (n, L))[:, 1:])
        payload = payload.at[:, :L - 1, 2].set(rslot)
        sizes = sizes.at[:, :L - 1].set(
            jnp.broadcast_to(sz_l, (n, L))[:, 1:])

        # Accelerated sends: drain the lowest queued level, `accel` peers at
        # once (getRemainingPeers(acceleratedCallsCount), :444-449).
        accel_pending = p.accel_pending
        if self.accel > 0:
            ac = self.accel
            lsb = accel_pending & -accel_pending
            fl = jnp.where(lsb > 0,
                           31 - jax.lax.clz(jnp.maximum(lsb, 1)),
                           0).astype(jnp.int32)                # [N]
            fhalf = jnp.maximum(halfs[fl], 1)
            frem = gather2d(remaining, ids, fl)
            fpos = gather2d(pos, ids, fl)
            k_idx = jnp.arange(ac, dtype=jnp.int32)[None, :]
            fsend = (fl > 0) & active
            fok = fsend[:, None] & (k_idx < jnp.minimum(frem, ac)[:, None])
            fpeer = self._peer_at(p.seed, ids[:, None],
                                  jnp.broadcast_to(fl[:, None], (n, ac)),
                                  (fpos[:, None] + k_idx) % fhalf[:, None])
            koff = L - 1
            dest = dest.at[:, koff:koff + ac].set(
                jnp.where(fok, fpeer, -1))
            payload = payload.at[:, koff:koff + ac, 0].set(fl[:, None])
            payload = payload.at[:, koff:koff + ac, 1].set(fin[:, None])
            payload = payload.at[:, koff:koff + ac, 2].set(rslot)
            sizes = sizes.at[:, koff:koff + ac].set(
                (1 + fhalf // 8 + 96)[:, None])
            nsent = jnp.sum(fok, axis=1).astype(jnp.int32)
            pos = set2d(pos, ids, fl, (fpos + nsent) % fhalf, ok=fsend)
            remaining = set2d(remaining, ids, fl,
                              jnp.maximum(frem - nsent, 0), ok=fsend)
            accel_pending = jnp.where(fsend, accel_pending & ~lsb,
                                      accel_pending)

        # Snapshot pool: senders record their V row for this round slot.
        wrote = jnp.any(dest >= 0, axis=1)
        pool = set_rows(p.pool, ids, jnp.full((n,), rslot, jnp.int32),
                        p.verified, ok=wrote)

        out = empty_outbox(self.cfg).replace(dest=dest, payload=payload,
                                             size=sizes)
        return p.replace(pos=pos, remaining=remaining, pool=pool,
                         accel_pending=accel_pending), out

    # ---------------------------------------------------------------- misc

    def done(self, pstate, nodes):
        return jnp.all(nodes.down | (nodes.done_at > 0))


def cont_if_gsf(net, pstate):
    """newConfIf (GSFSignature.java:676-688): continue while any live node
    is below the threshold."""
    live = ~net.nodes.down
    return jnp.any(live & (net.nodes.done_at == 0))
