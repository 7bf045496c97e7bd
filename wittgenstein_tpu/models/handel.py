"""Handel — practical multi-signature aggregation for large Byzantine
committees (arXiv:1906.05132).  The flagship protocol.

Reference: protocols/Handel.java (1054 lines).  Mechanism recap (SURVEY.md
§2.4): every node runs log2(N) binary-tree levels; per level it periodically
sends its best aggregate to one peer (round-robin through an emission list
ordered by the receivers' reception ranks, Handel.java:940-948,:991-1013);
incoming aggregates queue for verification; every `pairingTime` ms a node
picks ONE signature to verify using a variable-size rank window with a
scoring function (bestToVerify, Handel.java:566-630), simulating the pairing
cost; verified aggregates merge into per-level incoming sets, propagate into
upper levels' outgoing sets, trigger fast-path sends on level completion
(:738-743), and finish the node at the threshold (:747-749).

TPU-native design (all shapes fixed, everything vmappable over seeds):

* Level ranges partition the id space.  Node i's level-l peer set is the
  sibling half of its 2^l-aligned block (allSigsAtLevel, Handel.java:667-680)
  — contiguous and DISJOINT across levels.  So ONE [N, W] uint32 bitset row
  per node stores all levels' state at once (W = N/32 words), and a level's
  view is a computed range mask.  Per-level objects disappear.
* `totalIncoming = lastAggVerified | verifiedIndSignatures` and
  `totalOutgoing(l) = totalIncoming & block_mask(i, l-1)` are identities in
  the reference (updateVerifiedSignatures, Handel.java:686-750), so both are
  derived, not stored.  All per-level cardinalities come from ONE
  popcount-per-level primitive: word-level population counts contracted
  against a word→level one-hot on the MXU (`_level_pc`), since every 32-bit
  word of a node's row belongs to exactly one level.
* Reception ranks: the reference shuffles the full node list per node into an
  [N, N] rank matrix (setReceivingRanks, :940-948).  Impossible at 1M nodes;
  instead rank(i, s) = bij_perm(hash(seed, i), s) — a keyed bijective
  permutation, recomputed in-kernel (SURVEY.md §7.4.6).  Verification
  demotion (receptionRanks[from] += N, :830-834) becomes a per-(node, sender)
  `demoted` bit: one demotion is remembered, repeats are rare and absorbed.
* Messages carry (level, flags, round-slot) only — 3 words.  Signature bits
  are reconstructed at delivery from a rotating per-sender snapshot pool
  `pool[N, R, W]` written at send time: exact send-time aggregates without
  per-destination bitset copies in the mailbox (the same memory trick as the
  reference's recomputed-latency envelopes, Envelope.java:45-56; a fast-path
  write inside a dissemination round can refresh the same slot early, which
  only makes in-flight data marginally fresher).
* The unbounded per-level verification queues `toVerifyAgg` become ONE flat
  pool of Q slots per node tagged with (sender, level, rank); a slot's sig
  row holds only its level's range bits, so no per-level copies exist.  One
  entry per (sender, level) — newer aggregates supersede older (supersets in
  practice); evict the highest-rank entry when full.  bestToVerify's
  curation drops non-improving entries each pairing tick, exactly like the
  reference (:597-614).
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
from ..ops.flat import add2d, gather2d, gather_rows, set2d, set_rows
from ._levels import (LevelMixin, StaticScheduleMixin,
                      get_bit_rows as _get_bit_rows,
                      keyed_level_peer, merge_bounded_queue, sibling_base)

TAG_RANK = 0x48524E4B     # reception-rank permutation keys
TAG_BAD = 0x48424144      # bad-node choice
TAG_START = 0x48535452    # desynchronized start draw
TAG_LEVEL = 0x484C564C    # random level pick in checkSigs
TAG_EMIT = 0x48454D49     # hashed emission-order permutation keys

U32 = jnp.uint32
BIG = np.int32(1 << 30)


_sibling_base = sibling_base  # shared geometry (_levels.sibling_base)


@struct.dataclass
class HandelState:
    seed: jnp.ndarray          # int32 scalar
    start_at: jnp.ndarray      # int32 [N] (desynchronizedStart, Handel:56-61)
    pairing: jnp.ndarray       # int32 [N] nodePairingTime (speedRatio-scaled)
    ver_ind: jnp.ndarray       # u32 [N, W] verifiedIndSignatures (+ own bit)
    last_agg: jnp.ndarray      # u32 [N, W] lastAggVerified, all levels packed
    finished_peers: jnp.ndarray  # u32 [N, W]
    blacklist: jnp.ndarray     # u32 [N, W]
    demoted: jnp.ndarray       # u32 [N, W] — reception-rank demotion bits
    q_from: jnp.ndarray        # int32 [N, Q]  (-1 = empty slot)
    q_lvl: jnp.ndarray         # int32 [N, Q]
    q_rank: jnp.ndarray        # int32 [N, Q]
    q_bad: jnp.ndarray         # bool [N, Q]
    # The queued sig rows, stored as `state_split` node-range PIECES of
    # [N/P, Q, W] each (P == 1 -> a 1-tuple; layouts identical,
    # bit-equal for any P).  Same motivation as EngineConfig.box_split:
    # each piece's flat gathers index Ns*Q*W int32 cells, which must
    # stay below 2^31 (the constructor checks it), and the receive merge
    # and verification scoring compute their [*, Q|S, W] transients per
    # piece, bounding peak memory at the 32k exact tier.
    q_sig: tuple               # P x u32 [N/P, Q, W] — entry's level bits
    pool: jnp.ndarray          # u32 [N, R, W] — outgoing snapshots per round
    emission: jnp.ndarray      # int32 [N, N] — per-level sorted receiver ids
    pos: jnp.ndarray           # int32 [N, L] — posInLevel round-robin pointer
    curr_window: jnp.ndarray   # int32 [N]
    added_cycle: jnp.ndarray   # int32 [N] extraCycle countdown
    pend_from: jnp.ndarray     # int32 [N] in-flight verification (-1 = none)
    pend_level: jnp.ndarray    # int32 [N]
    pend_bad: jnp.ndarray      # bool [N]
    pend_sig: jnp.ndarray      # u32 [N, W]
    pend_at: jnp.ndarray       # int32 [N] — apply time
    fast_pending: jnp.ndarray  # int32 [N] — level bitmask of queued
    #                            fast-path sends (drained lowest-first,
    #                            one level per ms)
    sigs_checked: jnp.ndarray  # int32 [N]
    msg_filtered: jnp.ndarray  # int32 [N]
    evicted: jnp.ndarray       # int32 scalar — queue evictions (diagnostic)


@register
class Handel(LevelMixin, StaticScheduleMixin):
    """Parameters mirror Handel.HandelParameters (Handel.java:22-142).

    ``mode="cardinal"`` dispatches to the O(N*L)-state tier-3 variant
    (models/handel_cardinal.py, SCALE.md): same protocol semantics under
    count-based per-level aggregation, no O(N^2) state."""

    # Every unicast dest comes from a level peer set — the SIBLING half
    # of the node's 2^l-aligned block (models/_levels.py), which never
    # contains the node itself — so the latency model's floor licenses
    # superstep windows beyond 2 (core/network.unicast_floor_ms).
    may_self_send = False

    def __new__(cls, *args, mode="exact", **kwargs):
        if cls is Handel and mode == "cardinal":
            from .handel_cardinal import HandelCardinal
            obj = object.__new__(HandelCardinal)
            # Not a Handel subclass, so Python will not auto-call
            # __init__ on the returned object — do it here.  Cardinal
            # mode accepts the shared parameter subset; exact-only scale
            # switches (emission_mode, snapshot_pool, ...) are rejected
            # by its signature.
            obj.__init__(*args, **kwargs)
            return obj
        if mode not in ("exact", "cardinal"):
            raise ValueError(f"unknown Handel mode {mode!r}")
        return super().__new__(cls)

    def __init__(self, node_count=2048, threshold=None, pairing_time=3,
                 level_wait_time=50, extra_cycle=10,
                 dissemination_period_ms=10, fast_path=10, nodes_down=0,
                 node_builder_name=None, network_latency_name=None,
                 desynchronized_start=0, window_initial=16, window_min=1,
                 window_max=128, queue_cap=16, inbox_cap=16, horizon=512,
                 emission_lookahead=8, byzantine_suicide=False,
                 hidden_byzantine=False, emission_mode=None,
                 snapshot_pool=None, prefix_pc=None, pallas_merge=None,
                 state_split=1, mode="exact"):
        # `mode` is consumed by __new__ ("cardinal" dispatches to
        # HandelCardinal before this body runs); it reaches here only as
        # "exact".
        if node_count & (node_count - 1):
            raise ValueError("we support only power-of-two node counts "
                             "(Handel.java:119-121)")
        # Scale switches (SURVEY.md §7.4.6: stored [N, N] matrices cannot
        # exist at large N — recompute from hashes instead):
        # * emission_mode "stored" keeps the reference-exact emission lists
        #   (receivers sorted by the rank they assign to the sender,
        #   Handel.java:991-1013) as an [N, N] matrix; "hashed" derives the
        #   emission order from a keyed bijective permutation of the level
        #   range — O(1) state, but plain randomized round-robin: the
        #   rank-prioritized ordering (a convergence optimization) is lost.
        # * snapshot_pool False drops the [N, R, W] send-time snapshot pool;
        #   deliveries then reconstruct the aggregate from the sender's
        #   CURRENT state (marginally fresher than sent — the same
        #   direction of drift the pool's fast-path refresh already has).
        # Defaults cut over past 32768 nodes — exactly where the stored
        # matrix was previously a hard error, so configurations that ran
        # before keep their reference-exact semantics unchanged.
        if emission_mode is None:
            emission_mode = "stored" if node_count <= 32768 else "hashed"
        if emission_mode not in ("stored", "hashed"):
            raise ValueError(f"unknown emission_mode {emission_mode!r}")
        if snapshot_pool is None:
            snapshot_pool = node_count <= 32768
        if emission_mode == "stored" and node_count > 32768:
            raise ValueError("stored emission lists are O(N^2); use "
                             "emission_mode='hashed' past 32768 nodes")
        self.emission_mode = emission_mode
        self.snapshot_pool = snapshot_pool
        # Fused Pallas delivery-merge + verification-scoring kernels
        # (ops/pallas_merge.py, ops/pallas_score.py) — bit-identical to
        # the XLA paths (tests/test_pallas_merge.py, test_pallas_score
        # .py, test_handel.py::test_pallas_merge_path_bit_equal); CPU
        # runs with pallas_merge=True go through the interpreter.
        # Shared auto-default policy (resolve_pallas_default).
        from ..ops.pallas_merge import resolve_pallas_default
        self.pallas_merge = resolve_pallas_default(pallas_merge)
        if self.pallas_merge and queue_cap + inbox_cap > 255:
            # The kernel's unique-key headroom (BIG0 + position); fail
            # at construction, not after a 10-minute backend init.
            raise ValueError(
                f"pallas_merge supports queue_cap + inbox_cap <= 255 "
                f"(got {queue_cap} + {inbox_cap}); pass "
                "pallas_merge=False for wider rows")
        # Past ~16k nodes the [N, W, L] word->level one-hot for the MXU
        # popcount contraction is gigabytes; the prefix-sum path computes
        # the SAME values (tested bit-equal) in O(N * W).
        self.prefix_pc = (node_count > 16384 if prefix_pc is None
                          else prefix_pc)
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
        self.emission_lookahead = emission_lookahead
        if (byzantine_suicide or hidden_byzantine) and not nodes_down:
            raise ValueError("byzantine attacks need nodes_down > 0 "
                             "(the attacker controls the down nodes)")
        self.byzantine_suicide = byzantine_suicide
        self.hidden_byzantine = hidden_byzantine
        # state_split: q_sig node-range pieces (see HandelState.q_sig).
        if node_count % state_split:
            raise ValueError(f"state_split {state_split} must divide "
                             f"node_count {node_count}")
        if state_split > 1 and (byzantine_suicide or hidden_byzantine):
            # The attack paths are O(N^2) sweeps only run at small N,
            # where splitting is never needed; keeping them unsplit
            # avoids blocking the queue-insert scatter.
            raise ValueError("state_split > 1 is for tier-2 scale runs; "
                             "byzantine attack modes require "
                             "state_split == 1")
        self.state_split = state_split
        self.builder = builders.get_by_name(node_builder_name)
        self.latency = latency_mod.get_by_name(network_latency_name)

        # The queue-merge sort key is rank * (Q + S + 1) + pos in int32
        # (see _merge_queue); ranks stay < 2*N even after demotion, so the
        # key is bounded by 2*N*(Q+S+1) — enforce it fits.
        if 2 * node_count * (queue_cap + inbox_cap + 1) >= 2 ** 31:
            raise ValueError(
                "queue-merge sort key would overflow int32: "
                f"2*{node_count}*({queue_cap}+{inbox_cap}+1) >= 2**31; "
                "reduce queue_cap/inbox_cap or node_count")
        # q_sig's flat gathers index Ns*Q*W int32 cells PER PIECE
        # (ops/flat.py); found the hard way at 65536 nodes x queue_cap
        # 16 (exactly 2^31).  state_split raises the ceiling
        # proportionally.
        _w = (node_count + 31) // 32
        _ns = node_count // state_split
        if _ns * queue_cap * _w >= 2 ** 31:
            raise ValueError(
                f"verification-queue flat index would overflow int32: "
                f"{_ns}*{queue_cap}*{_w} >= 2**31 per q_sig piece; "
                "reduce queue_cap or raise state_split (SCALE.md tier 2)")
        self.bits = max(1, int(math.log2(node_count)))
        self.levels = self.bits + 1            # levels 0..bits
        self.w = bitset.n_words(node_count)
        self.rounds = horizon // max(1, dissemination_period_ms) + 2
        # half[l] = size of the level-l peer range (0 for level 0).
        self.half = np.array([0] + [1 << (l - 1)
                                    for l in range(1, self.levels)],
                             np.int32)
        # K outbox slots: one per sending level (1..levels-1) + fast path.
        k = (self.levels - 1) + fast_path
        self.cfg = EngineConfig(n=node_count, horizon=horizon,
                                inbox_cap=inbox_cap, payload_words=3,
                                out_deg=k, bcast_slots=0)

    # ------------------------------------------------------------ primitives






    def _rank(self, seed, i_ids, s_ids):
        """Reception rank node i assigns to sender s (the [N, N] shuffled
        matrix of setReceivingRanks, Handel.java:940-948, as a keyed
        permutation)."""
        key = prng.hash3(seed, TAG_RANK, i_ids)
        return prng.bij_perm(key, s_ids, self.bits)

    def _emission_peer(self, seed, i_ids, level, pos):
        """Hashed emission order: the `pos`-th receiver of node i at
        `level` (replaces the stored per-(node, level) emission list,
        Handel.java:991-1013, for large N).  NOTE: the stored list is
        sorted by the rank receivers assign to the sender — a convergence
        optimization the keyed permutation does NOT reproduce; hashed mode
        is plain randomized round-robin (the GSF emission model)."""
        return jnp.minimum(
            keyed_level_peer(seed, TAG_EMIT, i_ids, level, pos),
            self.node_count - 1)

    def _byz_candidates(self, p, nodes, excl_bits):
        """Per (node, level) lowest-reception-rank byzantine (down) peer,
        excluding senders whose bit is set in `excl_bits` [N, W].  The
        adversary's peer scan of createSuicideByzantineSig
        (Handel.java:538-559) and HiddenByzantine.firstByzantine (:844-858),
        as masked per-level argmin sweeps over the contiguous level ranges.
        Returns ([N, L] rank — BIG when none, [N, L] id — -1 when none).
        O(N^2) work: only evaluated when an attack flag is on."""
        n, L = self.node_count, self.levels
        ids = jnp.arange(n, dtype=jnp.int32)
        br = jnp.full((n, L), BIG, jnp.int32)
        bi = jnp.full((n, L), -1, jnp.int32)
        for l in range(1, L):
            half = 1 << (l - 1)
            base = _sibling_base(ids, half)
            cand = base[:, None] + jnp.arange(half, dtype=jnp.int32)[None, :]
            rank = self._rank(p.seed, ids[:, None], cand) + \
                jnp.where(_get_bit_rows(p.demoted, cand), n, 0)
            ok = nodes.down[cand] & ~_get_bit_rows(excl_bits, cand)
            rank = jnp.where(ok, rank, BIG)
            pos = jnp.argmin(rank, axis=1)
            best = jnp.take_along_axis(rank, pos[:, None], axis=1)[:, 0]
            bid = jnp.take_along_axis(cand, pos[:, None], axis=1)[:, 0]
            br = br.at[:, l].set(best)
            bi = bi.at[:, l].set(jnp.where(best < BIG, bid, -1))
        return br, bi

    # ---------------------------------------------------------------- init

    def init(self, seed):
        n, w, L, Q = self.node_count, self.w, self.levels, self.queue_cap
        seed = jnp.asarray(seed, jnp.int32)
        nodes = self.builder.build(seed, n)
        ids = jnp.arange(n, dtype=jnp.int32)

        # chooseBadNodes (Network.java:52-64): nodes_down distinct random.
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

        # Emission lists: for each (node, level), receivers of the level
        # sorted by the rank THEY assign to us (Handel.java:991-1013), laid
        # out per node as concatenated levels (level l at columns
        # [2^(l-1), 2^l)); column 0 unused (level 0 has no peers).  In
        # hashed mode the order is a keyed permutation recomputed in-kernel
        # (see __init__) and no matrix exists.
        if self.emission_mode == "stored":
            emission = jnp.zeros((n, n), jnp.int32)
            for l in range(1, L):
                half = 1 << (l - 1)
                base = _sibling_base(ids, half)                   # [N]
                recv = base[:, None] + jnp.arange(half)[None, :]  # [N, half]
                key = self._rank(seed, recv,
                                 jnp.broadcast_to(ids[:, None], recv.shape))
                order = jnp.argsort(key * n + (recv - base[:, None]), axis=1)
                emission = emission.at[:, half:2 * half].set(
                    jnp.take_along_axis(recv, order, axis=1))
        else:
            emission = jnp.zeros((1, 1), jnp.int32)

        def zero_bits():
            # Fresh buffer per field: under donation the same buffer must
            # not appear twice in an executable's arguments.
            return jnp.zeros((n, w), U32)

        net = init_net(self.cfg, nodes, seed)
        pstate = HandelState(
            seed=seed, start_at=start_at, pairing=pairing,
            ver_ind=bitset.one_bit(ids, w), last_agg=zero_bits(),
            finished_peers=zero_bits(), blacklist=zero_bits(),
            demoted=zero_bits(),
            q_from=jnp.full((n, Q), -1, jnp.int32),
            q_lvl=jnp.zeros((n, Q), jnp.int32),
            q_rank=jnp.zeros((n, Q), jnp.int32),
            q_bad=jnp.zeros((n, Q), bool),
            q_sig=tuple(jnp.zeros((n // self.state_split, Q, w), U32)
                        for _ in range(self.state_split)),
            pool=(jnp.zeros((n, self.rounds, w), U32) if self.snapshot_pool
                  else jnp.zeros((1, 1, 1), U32)),
            emission=emission, pos=jnp.zeros((n, L), jnp.int32),
            curr_window=jnp.full((n,), self.window_initial, jnp.int32),
            added_cycle=jnp.full((n,), self.extra_cycle, jnp.int32),
            pend_from=jnp.full((n,), -1, jnp.int32),
            pend_level=jnp.zeros((n,), jnp.int32),
            pend_bad=jnp.zeros((n,), bool),
            pend_sig=jnp.zeros((n, w), U32),
            pend_at=jnp.zeros((n,), jnp.int32),
            fast_pending=jnp.zeros((n,), jnp.int32),
            sigs_checked=jnp.zeros((n,), jnp.int32),
            msg_filtered=jnp.zeros((n,), jnp.int32),
            evicted=jnp.asarray(0, jnp.int32),
        )
        return net, pstate

    # ---------------------------------------------------------------- step

    def step(self, p: HandelState, nodes, inbox, t, key, hints=None):
        h = hints or {}
        ids = jnp.arange(self.node_count, dtype=jnp.int32)
        active = (~nodes.down) & (t >= p.start_at + 1)
        onehot = None if self.prefix_pc else self._word_onehot(ids)
        subm = self._subword_masks(ids)
        hi = ids >> 5

        # Sub-scopes of the engine's `protocol.step` stage (core/network).
        with jax.named_scope("handel.receive"):
            p = self._receive(p, nodes, inbox, t)
        if h.get("verify", True):
            with jax.named_scope("handel.apply"):
                p, nodes = self._apply_pending(p, nodes, t, onehot, subm,
                                               hi)
            with jax.named_scope("handel.verify"):
                p = self._pick_verification(p, nodes, t, active, onehot,
                                            subm, hi)
        with jax.named_scope("handel.disseminate"):
            p, out = self._disseminate(p, nodes, t, active, onehot, subm,
                                       hi, periodic=h.get("periodic", True))
        return p, nodes, out

    # -- receive: queue incoming aggregates (onNewSig, Handel.java:753-786)

    def _receive(self, p: HandelState, nodes, inbox, t):
        n, w, L, Q = self.node_count, self.w, self.levels, self.queue_cap
        P = self.state_split
        ns = n // P
        ids = jnp.arange(n, dtype=jnp.int32)
        done = nodes.done_at > 0

        valid = inbox.valid                                   # [N, S]
        src = jnp.clip(inbox.src, 0, n - 1)
        level = jnp.clip(inbox.data[:, :, 0], 0, L - 1)
        flags = inbox.data[:, :, 1]
        rslot = jnp.clip(inbox.data[:, :, 2], 0, self.rounds - 1)

        # Filters (Handel.java:755-763): done -> counted; pre-start or
        # blacklisted sender -> silently ignored.
        blk = _get_bit_rows(p.blacklist, src)
        ok = valid & ~done[:, None] & (t >= p.start_at)[:, None] & ~blk
        filtered = jnp.sum(valid & done[:, None], axis=1).astype(jnp.int32)
        fin = ok & ((flags & 1) != 0)
        rank_all = self._rank(p.seed, ids[:, None], src) + \
            jnp.where(_get_bit_rows(p.demoted, src), n, 0)

        # Queue merge, vectorized across ALL slots at once, per q_sig
        # node-range piece (bounds the [ns, S|Q, W] transients — see
        # HandelState.q_sig).  The reference queues every incoming
        # aggregate in an unbounded per-level list (onNewSig :753-786);
        # this implementation bounds memory with the shared
        # bounded-queue policy (_levels.merge_bounded_queue): one entry
        # per (sender, level) — newest wins — keep the Q best
        # (lowest-reception-rank) candidates.
        parts = {k: [] for k in ("from", "lvl", "rank", "bad")}
        pieces, fin_parts = [], []
        ev = jnp.asarray(0, jnp.int32)
        for j in range(P):
            sl = slice(j * ns, (j + 1) * ns)
            src_j, level_j, ok_j = src[sl], level[sl], ok[sl]
            # levelFinished -> finishedPeers (Handel.java:770-772).
            fin_bits = jnp.where(fin[sl][..., None],
                                 bitset.one_bit(src_j, w), U32(0))
            fin_parts.append(jax.lax.reduce(
                fin_bits, U32(0), jax.lax.bitwise_or, (1,)))
            # Reconstruct sigs from the senders' snapshot pool (one flat
            # gather); pool-free mode reads the sender's CURRENT
            # aggregate instead (see __init__).
            if self.snapshot_pool:
                sig_all = gather_rows(p.pool, src_j, rslot[sl]) & \
                    self._sender_block_mask(src_j, level_j)
            else:
                sig_all = (p.last_agg | p.ver_ind)[src_j] & \
                    self._sender_block_mask(src_j, level_j)
            if self.pallas_merge:
                from ..ops.pallas_merge import merge_queue_pallas
                q_f, q_l, q_r, q_b, q_s, ev_j = merge_queue_pallas(
                    p.q_from[sl], p.q_lvl[sl], p.q_rank[sl],
                    p.q_bad[sl], p.q_sig[j], src_j, level_j,
                    rank_all[sl], ok_j, sig_all, q_cap=Q,
                    interpret=jax.default_backend() != "tpu")
            else:
                sel2, sel3, ev_j = merge_bounded_queue(
                    p.q_from[sl], p.q_lvl[sl], p.q_rank[sl], src_j,
                    level_j, rank_all[sl], ok_j, Q,
                    {"bad": (p.q_bad[sl], jnp.zeros_like(ok_j))},
                    {"sig": (p.q_sig[j], sig_all)})
                q_f, q_l, q_r, q_b, q_s = (sel2["from"], sel2["lvl"],
                                           sel2["rank"], sel2["bad"],
                                           sel3["sig"])
            parts["from"].append(q_f)
            parts["lvl"].append(q_l)
            parts["rank"].append(q_r)
            parts["bad"].append(q_b)
            pieces.append(q_s)
            ev = ev + ev_j

        def cat(xs):
            return xs[0] if P == 1 else jnp.concatenate(xs, axis=0)

        finished = p.finished_peers | cat(fin_parts)
        return p.replace(q_from=cat(parts["from"]),
                         q_lvl=cat(parts["lvl"]),
                         q_rank=cat(parts["rank"]),
                         q_bad=cat(parts["bad"]),
                         q_sig=tuple(pieces), finished_peers=finished,
                         msg_filtered=p.msg_filtered + filtered,
                         evicted=p.evicted + ev)

    # -- apply a finished verification (updateVerifiedSignatures, :686-750)

    def _apply_pending(self, p: HandelState, nodes, t, onehot, subm, hi):
        n, w, L = self.node_count, self.w, self.levels
        ids = jnp.arange(n, dtype=jnp.int32)
        due = (p.pend_from >= 0) & (t >= p.pend_at)

        vs_from, vs_level, vs_sig, vs_bad = (p.pend_from, p.pend_level,
                                             p.pend_sig, p.pend_bad)
        # Bad sig -> blacklist the sender (suicide attack, :690-699).
        bad = due & vs_bad
        blacklist = jnp.where(bad[:, None],
                              p.blacklist | bitset.one_bit(vs_from, w),
                              p.blacklist)
        ok = due & ~vs_bad

        lmask = self._range_mask_dyn(ids, vs_level)           # [N, W]
        from_bit = bitset.one_bit(jnp.maximum(vs_from, 0), w)
        ver_ind = jnp.where(ok[:, None], p.ver_ind | from_bit, p.ver_ind)

        # lastAgg(level) = sig if it intersects the old, else old | sig —
        # only when the combined set improves on verifiedInd (:710-724).
        old_agg_l = p.last_agg & lmask
        ver_l = ver_ind & lmask
        improves = (bitset.popcount(vs_sig | ver_l) >
                    bitset.popcount(ver_l))
        inter = bitset.intersects(old_agg_l, vs_sig)
        new_agg_l = jnp.where((improves & inter)[:, None], vs_sig,
                              jnp.where(improves[:, None],
                                        old_agg_l | vs_sig, old_agg_l))
        last_agg = jnp.where(ok[:, None],
                             (p.last_agg & ~lmask) | new_agg_l, p.last_agg)

        total_inc = last_agg | ver_ind
        inc_pc = self._level_pc(total_inc, onehot, subm, hi)  # [N, L]
        halfs = jnp.asarray(self.half)[None, :]               # [1, L]
        vs_half = jnp.where(vs_level > 0,
                            1 << jnp.clip(vs_level - 1, 0, 30), 0)
        vs_inc = gather2d(inc_pc, ids, vs_level)
        just_completed = ok & (vs_inc >= vs_half) & (vs_half > 0)

        # Fast path (:738-743): on level completion, EVERY upper level
        # whose outgoing set is complete sends to fast_path peers.  The
        # reference sends them all in the same event; here the qualifying
        # levels queue into a bitmask drained one level per ms (K-slot
        # budget) — a <=L-ms stagger, far below the dissemination period.
        fast_pending = p.fast_pending
        if self.fast_path > 0:
            og_size = 1 + jnp.cumsum(inc_pc, axis=1) - inc_pc  # sum l'<l
            og_complete = og_size >= halfs                     # [N, L]
            cand = (og_complete &
                    (jnp.arange(L)[None, :] > vs_level[:, None]) &
                    (halfs > 0) & just_completed[:, None])
            bits = jnp.sum(
                jnp.where(cand, jnp.int32(1) << jnp.arange(L)[None, :], 0),
                axis=1).astype(jnp.int32)
            fast_pending = fast_pending | bits

        # doneAt at threshold (:747-749).
        total_card = bitset.popcount(total_inc)
        done_now = (nodes.done_at == 0) & ok & (total_card >= self.threshold)
        nodes = nodes.replace(done_at=jnp.where(
            done_now, jnp.maximum(t, 1), nodes.done_at).astype(jnp.int32))

        p = p.replace(blacklist=blacklist, ver_ind=ver_ind,
                      last_agg=last_agg, fast_pending=fast_pending,
                      pend_from=jnp.where(due, -1, p.pend_from))
        return p, nodes

    # -- pick next signature to verify (checkSigs/bestToVerify, :566-630)

    def _pick_verification(self, p: HandelState, nodes, t, active,
                           onehot, subm, hi):
        n, w, L, Q = self.node_count, self.w, self.levels, self.queue_cap
        ids = jnp.arange(n, dtype=jnp.int32)
        due = (active & (p.pend_from < 0) &
               ((t - (p.start_at + 1)) % p.pairing == 0))

        total_inc = p.last_agg | p.ver_ind
        inc_pc = self._level_pc(total_inc, onehot, subm, hi)   # [N, L]
        ver_pc = self._level_pc(p.ver_ind, onehot, subm, hi)
        agg_pc = self._level_pc(p.last_agg, onehot, subm, hi)
        halfs = jnp.asarray(self.half)[None, :]

        rows = ids[:, None]
        filled = p.q_from >= 0                                 # [N, Q]
        elvl = p.q_lvl
        cur_size = gather2d(inc_pc, rows, elvl)                # [N, Q]
        blk = _get_bit_rows(p.blacklist, jnp.maximum(p.q_from, 0))

        # The W-wide queue work — sizeIfIncluded (:545-552) and the
        # score popcounts (:651-664) — runs per q_sig node-range piece
        # (bounds the [ns, Q, W] transients; see HandelState.q_sig),
        # emitting only [ns, Q] summaries.
        P = self.state_split
        ns = n // P
        s_inc_p, pc_sig_p, pc_sv_p, inter_agg_p = [], [], [], []
        for j in range(P):
            sl = slice(j * ns, (j + 1) * ns)
            sig = p.q_sig[j]                                  # [ns, Q, W]
            if self.pallas_merge:
                # Same switch as the delivery-merge kernel: one fused
                # pass instead of ~6 HBM round-trips over the sig plane
                # (ops/pallas_score.py, bit-equal by test).
                from ..ops.pallas_score import score_queue_pallas
                si, ps, pv, ia = score_queue_pallas(
                    sig, elvl[sl], ids[sl], total_inc[sl], p.ver_ind[sl],
                    p.last_agg[sl],
                    interpret=jax.default_backend() != "tpu")
                s_inc_p.append(si)
                pc_sig_p.append(ps)
                pc_sv_p.append(pv)
                inter_agg_p.append(ia)
                continue
            emask = self._range_mask_dyn(ids[sl][:, None], elvl[sl])
            inc_e = total_inc[sl][:, None, :] & emask
            ver_e = p.ver_ind[sl][:, None, :] & emask
            agg_e = p.last_agg[sl][:, None, :] & emask
            disj = ~bitset.intersects(sig, inc_e)
            merged = jnp.where(disj[..., None], sig | inc_e, sig)
            s_inc_p.append(bitset.popcount(merged | ver_e))
            pc_sig_p.append(bitset.popcount(sig))
            pc_sv_p.append(bitset.popcount(sig | ver_e))
            inter_agg_p.append(bitset.intersects(sig, agg_e))

        def cat(xs):
            return xs[0] if P == 1 else jnp.concatenate(xs, axis=0)

        s_inc = cat(s_inc_p)
        pc_sig = cat(pc_sig_p)
        pc_sig_ver = cat(pc_sv_p)
        inter_agg = cat(inter_agg_p)
        improving = filled & ~blk & (s_inc > cur_size)
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

        # score (:651-664) — from the per-piece popcount summaries.
        halfs_arr = jnp.asarray(self.half)
        agg_card_e = gather2d(agg_pc, rows, elvl)
        half_e = halfs_arr[elvl]
        sc_disj = agg_card_e + pc_sig
        sc_join = jnp.maximum(0, pc_sig_ver - agg_card_e)
        score = jnp.where(inter_agg, sc_join, sc_disj)
        score = jnp.where(agg_card_e >= half_e, 0, score)
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

        # byzantineSuicide (Handel.java:538-559, :577-583): if a still-
        # unblacklisted byzantine peer's rank falls inside the level's
        # verification window, the adversary plants an invalid signature
        # from it, and it preempts the level's honest pick.
        if self.byzantine_suicide:
            sbr, sbi = self._byz_candidates(p, nodes, p.blacklist)
            # Strict < here vs <= in the honest window test above is the
            # reference's own boundary convention (:545 `rank < maxRank`
            # vs :597 `rank <= windowIndex + currWindowSize`).
            s_ok = ((win_lo < BIG) &
                    (sbr < win_lo + p.curr_window[:, None]))   # [N, L]
            has_best = has_best | (s_ok & due[:, None])

        # chooseBestFromLevels (:788-790): uniform random non-empty level.
        cnt = jnp.sum(has_best, axis=1).astype(jnp.int32)
        r = prng.uniform_int(prng.hash3(p.seed, TAG_LEVEL, t), ids,
                             jnp.maximum(cnt, 1))
        csum = jnp.cumsum(has_best, axis=1).astype(jnp.int32)
        pick_level = jnp.argmax((csum == r[:, None] + 1) & has_best, axis=1)
        do = due & (cnt > 0)

        slot = gather2d(best_slot, ids, pick_level)
        vfrom = gather2d(p.q_from, ids, slot)
        vbad = gather2d(p.q_bad, ids, slot)
        vsig = cat([gather_rows(p.q_sig[j],
                                jnp.arange(ns, dtype=jnp.int32),
                                slot[j * ns:(j + 1) * ns])
                    for j in range(P)])
        # keep_entry: the picked QUEUE slot survives (an adversarial sig was
        # verified instead; the honest entry stays queued, :577-583,:905-913).
        keep_entry = jnp.zeros_like(do)

        if self.byzantine_suicide:
            use_s = do & gather2d(s_ok, ids, pick_level)
            s_id = gather2d(sbi, ids, pick_level)
            # An s_ok level may have no honest candidate at all; the planted
            # sig is then the only pick for it.
            vfrom = jnp.where(use_s, s_id, vfrom)
            vbad = vbad | use_s
            vsig = jnp.where(use_s[:, None], U32(0), vsig)
            keep_entry = keep_entry | use_s

        # HiddenByzantine (Handel.java:840-917): flood with valid but useless
        # single-signer aggregates from byzantine peers.  If a byzantine peer
        # outranks the picked signature, the adversary injects a 1-bit sig
        # from it; a rerun of bestToVerify then either verifies the plant
        # (wasting the pairing slot) or leaves it polluting the queue.
        if self.hidden_byzantine:
            hbr, hbi = self._byz_candidates(p, nodes,
                                            p.blacklist | total_inc)
            h_rank = gather2d(hbr, ids, pick_level)
            h_id = gather2d(hbi, ids, pick_level)
            honest = do & ~keep_entry
            # No re-attack while the previous plant for this (id, level) is
            # still queued (the `last`-in-toVerifyAgg check, :883-893).
            queued = jnp.any((p.q_from == h_id[:, None]) &
                             (p.q_lvl == pick_level[:, None]), axis=1)
            can = (honest & (h_id >= 0) & ~queued &
                   (h_rank < gather2d(p.q_rank, ids, slot)))   # :898-901
            # Rerun verdict: the plant is inside its own window; it beats an
            # outside-window pick outright, an inside pick only on score.
            # Plant score = aggregate card + 1 (disjoint single bit, :651-664).
            h_score = gather2d(agg_pc, ids, pick_level) + 1
            s_picked = gather2d(score, ids, slot)
            was_in = gather2d(in_ok, ids, pick_level)
            h_win = can & (~was_in | (h_score > s_picked))
            h_sig = bitset.one_bit(jnp.maximum(h_id, 0), w)
            vfrom = jnp.where(h_win, h_id, vfrom)
            vbad = vbad & ~h_win
            vsig = jnp.where(h_win[:, None], h_sig, vsig)
            keep_entry = keep_entry | h_win
            h_fail = can & ~h_win                               # :905-913

        # Window resize (:821-823): grow on good, shrink on bad, clamped to
        # [min, max] then to the level size.
        lsize = jnp.maximum(halfs_arr[pick_level], 1)
        grown = jnp.where(vbad, p.curr_window // 4, 2 * p.curr_window)
        new_win = jnp.clip(grown, self.window_min, self.window_max)
        curr_window = jnp.where(do, jnp.minimum(new_win, lsize),
                                p.curr_window)

        # Rank demotion (:830-834) — remembered as a bit.
        demoted = jnp.where(
            do[:, None],
            p.demoted | bitset.one_bit(jnp.maximum(vfrom, 0), w), p.demoted)

        # Curation sweep for due nodes + removal of the picked entry.
        q_from = jnp.where(due[:, None] & ~keep, -1, p.q_from)
        q_from = set2d(q_from, ids, slot, -1, ok=do & ~keep_entry)
        q_lvl, q_rank, q_bad, q_sig = p.q_lvl, p.q_rank, p.q_bad, p.q_sig

        if self.hidden_byzantine:
            # A failed attack leaves the plant in the queue (:905-913),
            # in a free slot or evicting the worst-ranked entry.
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
            q_bad = set2d(q_bad, ids, islot, False, ok=ins)
            # state_split == 1 enforced for attack modes (__init__).
            q_sig = (set_rows(q_sig[0], ids, islot, h_sig, ok=ins),)

        return p.replace(
            q_from=q_from, q_lvl=q_lvl, q_rank=q_rank, q_bad=q_bad,
            q_sig=q_sig, curr_window=curr_window, demoted=demoted,
            pend_from=jnp.where(do, vfrom, p.pend_from),
            pend_level=jnp.where(do, pick_level, p.pend_level),
            pend_bad=jnp.where(do, vbad, p.pend_bad),
            pend_sig=jnp.where(do[:, None], vsig, p.pend_sig),
            pend_at=jnp.where(do, t + p.pairing, p.pend_at),
            sigs_checked=p.sigs_checked + do.astype(jnp.int32))

    # -- dissemination (doCycle, :331-343,:470-504) + outbox assembly

    def _disseminate(self, p: HandelState, nodes, t, active,
                     onehot, subm, hi, periodic=True):
        n, w, L = self.node_count, self.w, self.levels
        ids = jnp.arange(n, dtype=jnp.int32)
        done = nodes.done_at > 0
        halfs_np = self.half                                   # numpy [L]
        halfs = jnp.asarray(halfs_np)[None, :]
        total_inc = p.last_agg | p.ver_ind
        bad_bits = p.finished_peers | p.blacklist
        rslot = (t // self.period) % self.rounds
        # Non-periodic ms can only populate the fast-path slots: emit a
        # NARROW outbox covering just those columns (slot ids preserved
        # via Outbox.slot0, so latency draws stay bit-identical) — the
        # engine's binning sort then runs over n*fast_path entries
        # instead of n*out_deg.
        K = self.cfg.out_deg if periodic else max(1, self.fast_path)
        koff = L - 1 if periodic else 0
        dest = jnp.full((n, K), -1, jnp.int32)
        payload = jnp.zeros((n, K, 3), jnp.int32)
        sizes = jnp.ones((n, K), jnp.int32)

        # `periodic=False` (static phase hint, see `scan_chunk`): no node
        # can be on a period boundary this ms, so the per-period
        # dissemination block below — level popcounts, open-level tests
        # and the emission-list lookahead — reduces to the identity it
        # would have computed (send_l all-False, pos/added_cycle
        # unchanged, level outbox slots empty) and is skipped entirely.
        # Only the fast path (which drains every ms) remains.
        if periodic:
            per_due = active & ((t - (p.start_at + 1)) % self.period == 0)
            # extraCycle (:331-343): done nodes keep disseminating for
            # added_cycle more periods.
            send_ok = per_due & (~done | (p.added_cycle > 0))
            added_cycle = jnp.where(per_due & done,
                                    jnp.maximum(p.added_cycle - 1, 0),
                                    p.added_cycle)

            inc_pc = self._level_pc(total_inc, onehot, subm, hi)  # [N, L]
            og_size = 1 + jnp.cumsum(inc_pc, axis=1) - inc_pc  # sum l'<l + own
            og_complete = og_size >= halfs
            inc_complete = inc_pc >= halfs
            lvl_idx = jnp.arange(L, dtype=jnp.int32)[None, :]
            is_open = ((t >= (lvl_idx - 1) * self.level_wait_time) |
                       og_complete) & (halfs > 0)

            # Candidate existence per level: any waited peer not finished and
            # not blacklisted (else outgoingFinished, :470-504).
            fin_pc = self._level_pc(bad_bits, onehot, subm, hi)
            any_cand = (halfs - fin_pc) > 0

            # Round-robin pick: next non-finished peer in emission order,
            # looking ahead `look` entries from posInLevel.
            look = self.emission_lookahead
            half_cols = jnp.maximum(halfs, 1)                  # [1, L]
            offs = (p.pos[:, :, None] + jnp.arange(look)[None, None, :]) % \
                half_cols[:, :, None]                          # [N, L, k]
            if self.emission_mode == "stored":
                cols = jnp.minimum(half_cols[:, :, None] + offs, n - 1)
                cand_ids = gather2d(p.emission, ids[:, None, None], cols)
            else:
                cand_ids = self._emission_peer(p.seed, ids[:, None, None],
                                               lvl_idx[:, :, None], offs)
            okc = ~_get_bit_rows(bad_bits, cand_ids)           # [N, L, k]
            found = jnp.any(okc, axis=2)
            first = jnp.argmax(okc, axis=2)
            # candidate at the first ok position (max trick: invalid -> -1).
            peer = jnp.max(jnp.where(
                okc & (jnp.arange(look)[None, None, :] == first[..., None]),
                cand_ids, -1), axis=2)                         # [N, L]

            send_l = send_ok[:, None] & is_open & any_cand & found
            adv = per_due[:, None] & is_open & any_cand
            pos = jnp.where(adv,
                            (p.pos + jnp.where(found, first + 1, look)) %
                            half_cols, p.pos)

            # SendSigs size (bytes): 1 + expected/8 + 96*2 (:255-259).
            sz_l = 1 + halfs // 8 + 192                        # [1, L]
            dest = dest.at[:, :L - 1].set(jnp.where(send_l, peer, -1)[:, 1:])
            payload = payload.at[:, :L - 1, 0].set(lvl_idx[:, 1:])
            payload = payload.at[:, :L - 1, 1].set(
                inc_complete.astype(jnp.int32)[:, 1:])
            payload = payload.at[:, :L - 1, 2].set(rslot)
            sizes = sizes.at[:, :L - 1].set(
                jnp.broadcast_to(sz_l, (n, L))[:, 1:])
        else:
            added_cycle = p.added_cycle
            pos = p.pos

        # Fast-path sends on level completion (:738-743), bypassing the
        # period gate: drain the lowest queued level's fast_path peers.
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
            if self.emission_mode == "stored":
                fcols = jnp.minimum(fhalf[:, None] + foffs, n - 1)
                fids = gather2d(p.emission, ids[:, None], fcols)
            else:
                fids = self._emission_peer(p.seed, ids[:, None],
                                           fl[:, None], foffs)
            fok = ~_get_bit_rows(bad_bits, fids)
            fsend = (fl > 0) & active & ~done
            fdest = jnp.where(fsend[:, None] & fok, fids, -1)
            dest = dest.at[:, koff:koff + fp].set(fdest)
            payload = payload.at[:, koff:koff + fp, 0].set(fl[:, None])
            payload = payload.at[:, koff:koff + fp, 2].set(rslot)
            sizes = sizes.at[:, koff:koff + fp].set(
                (1 + fhalf // 8 + 192)[:, None])
            pos = add2d(pos, ids, jnp.maximum(fl, 1),
                        jnp.where(fsend, jnp.sum(fok, axis=1), 0))
            fast_pending = jnp.where(fsend, fast_pending & ~lsb,
                                     fast_pending)
            # Done nodes never fast-path again; drop stale queued levels.
            fast_pending = jnp.where(done, 0, fast_pending)

        # Snapshot pool: any sender this ms records its current total_inc;
        # receivers mask out their level's view at delivery.
        if self.snapshot_pool:
            wrote = jnp.any(dest >= 0, axis=1)
            pool = set_rows(p.pool, ids, jnp.full((n,), rslot, jnp.int32),
                            total_inc, ok=wrote)
        else:
            pool = p.pool

        # slot0 clamped into [0, out_deg): with fast_path == 0 the narrow
        # non-periodic outbox is a single always-empty column (dest all
        # -1), and slot0 == L-1 == out_deg would collide its stable
        # latency-key slot id with the next node's slot 0 (ADVICE r3).
        out = empty_outbox(self.cfg, k=K,
                           slot0=0 if periodic else
                           min(L - 1, self.cfg.out_deg - 1)).replace(
            dest=dest, payload=payload, size=sizes)
        return p.replace(pos=pos, added_cycle=added_cycle, pool=pool,
                         fast_pending=fast_pending), out

    # ---------------------------------------------------------------- misc

    def done(self, pstate, nodes):
        return jnp.all(nodes.down | (nodes.done_at > 0))


def cont_if_handel(net, pstate):
    """Handel.newContIf (Handel.java:1040-1049): run while any live node is
    not done or still owes extra dissemination cycles."""
    live = ~net.nodes.down
    return jnp.any(live & ((net.nodes.done_at == 0) |
                           (pstate.added_cycle > 0)))
