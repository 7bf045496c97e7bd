"""Ethereum Proof-of-Work mining with honest and selfish-miner strategies.

Reference: protocols/ethpow/ — ETHPoW.java (375), ETHMiner.java (309),
ETHSelfishMiner.java (138), ETHSelfishMiner2.java (104).  Mechanism
(SURVEY.md §2.4): every miner runs a 10 ms periodic mining tick — a
bernoulli draw with p = solveIn10ms(difficulty) from its hash power
(ETHMiner.mine10ms :118-129, solveIn10ms :225-231); blocks carry
Constantinople difficulty + bomb (ETHPoW.calculateDifficulty :283-296) and
up to two uncles chosen from received sibling blocks (possibleUncles
:66-115, UncleCmp :97-115); fork choice is total difficulty
(POWBlockComparator :300-310, best :337-348); strategy hooks
(sendMinedBlock / switchMining / onMinedBlock / onReceivedBlock) implement
the Eyal-Sirer selfish miner and a total-difficulty-aware variant
(ETHSelfishMiner.java:28-115, ETHSelfishMiner2.java:12-80).

TPU-native design:
* One engine tick = `tick_ms` (default 10) simulated ms — the reference's
  mining period; latencies are ceil-scaled into ticks (class _TickScaled).
* Blocks live in the shared arena (core/blockchain.py) + POW columns:
  scaled difficulty (raw / 2^21 fits int32; relative error < 1e-8), total
  difficulty relative to genesis as an exact int32 fixed-point pair, two
  uncle slots.
* Strategies are a per-node enum {HONEST, SELFISH, SELFISH2} executed with
  masks — all miners run the same vectorized step.
* sendAll of a block is one broadcast-table entry (O(1) state); multi-block
  releases (sendAllMined) drain one block per tick, parents first — a
  <= few-tick stagger, negligible against the ~13 s block interval.
* Selfish miners extend their private chain (mine_private) exactly as the
  reference's startNewMining(privateMinerBlock); "they won" switches the
  mining base back to the public head.

Blockchain sims run at 5-10k nodes max in the reference (CasperIMD.java:714)
and N~10 miners here, so the TPU win comes from vmapping seeds/sweeps, not
from node-axis width.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..core import blockchain as bc
from ..core import builders
from ..core import latency as latency_mod
from ..core.protocol import register
from ..core.state import EngineConfig, empty_outbox, init_net
from ..ops import bitset, prng

U32 = jnp.uint32
TAG_MINE = 0x504F5731

HONEST, SELFISH, SELFISH2, AGENT = 0, 1, 2, 3
STRATEGIES = {"": HONEST, None: HONEST, "ETHMiner": HONEST,
              "ETHSelfishMiner": SELFISH, "ETHSelfishMiner2": SELFISH2,
              "ETHAgentMiner": AGENT, "ETHMinerAgent": AGENT}

GENESIS_HEIGHT = 7_951_081                  # POWBlock genesis (:158-165)
GENESIS_DIFF_RAW = 1_949_482_043_446_410
DIFF_SHIFT = 21                             # raw difficulty / 2^21 -> int32
GENESIS_DIFF_S = int(round(GENESIS_DIFF_RAW / 2 ** DIFF_SHIFT))
TOTAL_HASH_POWER = 200 * 1024               # GH/s (ETHPoW.init :72)


def difficulty_s(fd_s, father_height, gap, father_has_uncles):
    """Constantinople difficulty + bomb (calculateDifficulty,
    ETHPoW.java:283-296) in 2^DIFF_SHIFT-scaled int32 units.

    ``gap = (ts - father.proposalTime_ms) // 9000``; both sides floor the
    /2048 step, so the only divergence from the reference's long math is
    the scaled representation itself (<= a few scaled units per block —
    golden-tested against EthPoWTest.java:33-70's published values in
    tests/test_ethpow.py)."""
    y = jnp.where(father_has_uncles, 2, 1)
    ugap = jnp.maximum(-99, y - gap)
    diff = (fd_s // 2048) * ugap
    # The bomb period counts from the FATHER's height — the reference is
    # literally `periods = (father.height - 4_999_999L) / 100_000L`
    # (calculateDifficulty :291); an earlier in-line version of this code
    # wrongly used the child height (father + 1), off by one at period
    # boundaries.
    periods = (father_height - 4_999_999) // 100_000
    # periods <= 1 falls back to `diff`, not 0 — the reference's own
    # quirk (:290-293); unreachable at this genesis height (periods ~ 29)
    # but kept formula-for-formula.
    bomb = jnp.where(periods > 1,
                     jnp.where(periods - 2 >= DIFF_SHIFT,
                               jnp.int32(1) << jnp.clip(
                                   periods - 2 - DIFF_SHIFT, 0, 30), 0),
                     diff)
    return fd_s + diff + bomb


class _TickScaled:
    """Wraps a ms latency model: output is ceil-divided into engine ticks."""

    def __init__(self, inner, tick_ms):
        self.inner = inner
        self.tick_ms = tick_ms
        self.name = f"TickScaled({inner!r}, {tick_ms})"

    def validate(self, nodes):
        v = getattr(self.inner, "validate", None)
        if v is not None:
            v(nodes)

    def extended(self, nodes, src, dst, delta):
        ms = self.inner.extended(nodes, src, dst, delta)
        return -(-ms // self.tick_ms)

    def latency_floor_ms(self):
        # Ceil-scaling is monotone, so the wrapped floor ceil-divides
        # through (core/latency.py contract; >= 1 either way).
        from ..core.latency import latency_floor_ms
        return max(1, -(-latency_floor_ms(self.inner) // self.tick_ms))

    def __repr__(self):
        return self.name



def _td_gt(p, a, b):
    """total_difficulty[a] > total_difficulty[b], exact (int32 pair)."""
    aw_, bw_ = jnp.maximum(a, 0), jnp.maximum(b, 0)
    return ((p.td_hi[aw_] > p.td_hi[bw_]) |
            ((p.td_hi[aw_] == p.td_hi[bw_]) & (p.td_lo[aw_] > p.td_lo[bw_])))


def _td_eq(p, a, b):
    aw_, bw_ = jnp.maximum(a, 0), jnp.maximum(b, 0)
    return (p.td_hi[aw_] == p.td_hi[bw_]) & (p.td_lo[aw_] == p.td_lo[bw_])


@struct.dataclass
class PoWState:
    seed: jnp.ndarray
    arena: bc.Arena
    diff_s: jnp.ndarray        # int32 [A] — scaled block difficulty
    # Total difficulty above genesis, EXACT fixed point: value =
    # td_hi * 2^30 + td_lo in 2^DIFF_SHIFT raw units (float32 ulp outgrows
    # per-block deltas after a few thousand blocks; the selfish-miner
    # experiments run for hundreds of simulated hours).
    td_hi: jnp.ndarray         # int32 [A]
    td_lo: jnp.ndarray         # int32 [A], in [0, 2^30)
    u1: jnp.ndarray            # int32 [A] uncle slots (-1 = none)
    u2: jnp.ndarray
    received: jnp.ndarray      # u32 [N, Aw]
    head: jnp.ndarray          # int32 [N]
    min_father: jnp.ndarray    # int32 [N] (-1 = not mining)
    min_u1: jnp.ndarray        # int32 [N]
    min_u2: jnp.ndarray
    min_diff: jnp.ndarray      # int32 [N] scaled difficulty of the candidate
    thr: jnp.ndarray           # f32 [N] solveIn10ms probability
    mined_unsent: jnp.ndarray  # u32 [N, Aw] — minedToSend
    release: jnp.ndarray       # u32 [N, Aw] — queued sendAll broadcasts
    private_blk: jnp.ndarray   # int32 [N] (-1 = none)
    mine_private: jnp.ndarray  # bool [N] — mining base is the private chain
    others_head: jnp.ndarray   # int32 [N]
    hash_power: jnp.ndarray    # int32 [N] GH/s
    strategy: jnp.ndarray      # int32 [N]


@register
class ETHPoW:
    """Parameters mirror ETHPoWParameters (ETHPoW.java:14-42).  Node 0 is
    the observer (no hash power); the byzantine miner is node 1 (:66-68)."""

    def __init__(self, number_of_miners=10, byz_class_name=None,
                 byz_mining_ratio=0.0, node_builder_name=None,
                 network_latency_name=None, tick_ms=10, capacity=4096,
                 inbox_cap=2, bcast_slots=12, horizon=1024):
        if byz_class_name not in STRATEGIES:
            raise ValueError(f"unknown byzantine miner {byz_class_name!r}; "
                             f"known: {sorted(k for k in STRATEGIES if k)}")
        self.n_miners = number_of_miners
        self.node_count = number_of_miners
        self.byz_strategy = STRATEGIES[byz_class_name]
        # Any non-empty byzClassName gives node 1 the byz hash power — the
        # reference's honest control experiment uses byzClassName=ETHMiner
        # with a nonzero ratio (ETHPoW.java:72-90, tryMiner).
        self.has_byz = byz_class_name not in (None, "")
        self.byz_ratio = byz_mining_ratio if self.has_byz else 0.0
        self.tick_ms = tick_ms
        # Round up to whole bitset words: block-set masks reshape [A] as
        # [aw, 32] (e.g. the AGENT overtaken-publish path).
        self.capacity = -(-capacity // 32) * 32
        self.aw = bc.n_words(self.capacity)
        self.builder = builders.get_by_name(node_builder_name)
        self.latency = _TickScaled(
            latency_mod.get_by_name(network_latency_name), tick_ms)
        self.cfg = EngineConfig(
            n=self.node_count, horizon=horizon, inbox_cap=inbox_cap,
            payload_words=1, out_deg=1, bcast_slots=bcast_slots)

    def init(self, seed):
        n, a, aw = self.node_count, self.capacity, self.aw
        seed = jnp.asarray(seed, jnp.int32)
        nodes = self.builder.build(seed, n)
        ids = jnp.arange(n, dtype=jnp.int32)

        # Hash power split (ETHPoW.init :71-75); node 0 observes (0 GH/s).
        byz_hp = int(TOTAL_HASH_POWER * self.byz_ratio)
        honest_ct = max(1, (self.n_miners - 1) - (1 if byz_hp else 0))
        honest_hp = (TOTAL_HASH_POWER - byz_hp) // honest_ct
        hp = jnp.full((n,), honest_hp, jnp.int32)
        hp = hp.at[0].set(0)
        strategy = jnp.zeros((n,), jnp.int32)
        if self.has_byz and n > 1:
            hp = hp.at[1].set(byz_hp)
            strategy = strategy.at[1].set(self.byz_strategy)

        arena = bc.make_arena(a, genesis_height=GENESIS_HEIGHT)
        net = init_net(self.cfg, nodes, seed)
        genesis_bit = bitset.one_bit(jnp.zeros((n,), jnp.int32), aw)
        return net, PoWState(
            seed=seed, arena=arena,
            diff_s=jnp.zeros((a,), jnp.int32).at[0].set(GENESIS_DIFF_S),
            td_hi=jnp.zeros((a,), jnp.int32),
            td_lo=jnp.zeros((a,), jnp.int32),
            u1=jnp.full((a,), -1, jnp.int32),
            u2=jnp.full((a,), -1, jnp.int32),
            received=genesis_bit,
            head=jnp.zeros((n,), jnp.int32),
            min_father=jnp.full((n,), -1, jnp.int32),
            min_u1=jnp.full((n,), -1, jnp.int32),
            min_u2=jnp.full((n,), -1, jnp.int32),
            min_diff=jnp.zeros((n,), jnp.int32),
            thr=jnp.zeros((n,), jnp.float32),
            mined_unsent=jnp.zeros((n, aw), U32),
            release=jnp.zeros((n, aw), U32),
            private_blk=jnp.full((n,), -1, jnp.int32),
            mine_private=jnp.zeros((n,), bool),
            others_head=jnp.zeros((n,), jnp.int32),
            hash_power=hp, strategy=strategy)

    # ------------------------------------------------------------ helpers

    def _best(self, p, cur, alt, me):
        """Fork choice by total difficulty (best :337-348 + comparator
        :300-310): invalid loses; strict improvement wins; ties go to own
        blocks."""
        a_ok = (alt >= 0) & p.arena.valid[jnp.maximum(alt, 0)]
        better = a_ok & (_td_gt(p, alt, cur) |
                         (_td_eq(p, alt, cur) &
                          (p.arena.producer[jnp.maximum(alt, 0)] == me)))
        return jnp.where(better, alt, cur)

    def _depth(self, p, b, me):
        """Own blocks mined in a row from b (ETHMiner.depth :55-64)."""
        def cond(st):
            cur, _ = st
            return jnp.any((cur >= 0) &
                           (p.arena.producer[jnp.maximum(cur, 0)] == me))

        def body(st):
            cur, d = st
            step = (cur >= 0) & (p.arena.producer[jnp.maximum(cur, 0)] == me)
            return (jnp.where(step, p.arena.parent[jnp.maximum(cur, 0)], cur),
                    d + step.astype(jnp.int32))

        _, d = jax.lax.while_loop(cond, body,
                                  (b, jnp.zeros_like(b)))
        return d

    def _release_chain(self, p, top, me):
        """Queue `top` and its own unsent ancestors for broadcast
        (the sendBlock loop, ETHSelfishMiner.java:105-110)."""
        aw = self.aw

        def cond(st):
            cur, _, _ = st
            unsent = bitset.get_bit(st[1], jnp.maximum(cur, 0))
            return jnp.any((cur >= 0) &
                           (p.arena.producer[jnp.maximum(cur, 0)] == me) &
                           unsent)

        def body(st):
            cur, unsent_b, rel = st
            on = (cur >= 0) & \
                (p.arena.producer[jnp.maximum(cur, 0)] == me) & \
                bitset.get_bit(unsent_b, jnp.maximum(cur, 0))
            bit = jnp.where(on[:, None],
                            bitset.one_bit(jnp.maximum(cur, 0), aw), U32(0))
            return (jnp.where(on, p.arena.parent[jnp.maximum(cur, 0)], cur),
                    unsent_b & ~bit, rel | bit)

        _, unsent, rel = jax.lax.while_loop(
            cond, body, (top, p.mined_unsent, p.release))
        return unsent, rel

    def _possible_uncle_of(self, p, father, b):
        """isPossibleUncle against a block mined on `father` (:253-262):
        height within 7 of the new block, parent on father's chain."""
        hb = p.arena.height[jnp.maximum(b, 0)]
        hf = p.arena.height[jnp.maximum(father, 0)]
        in_range = (b >= 0) & (father >= 0) & (hb <= hf) & (hb >= hf - 6)
        anc = bc.walk_to_height(p.arena, father, hb)
        sib = p.arena.parent[jnp.maximum(anc, 0)] == \
            p.arena.parent[jnp.maximum(b, 0)]
        return in_range & sib & (anc != b)

    def _start_mining(self, p, need, t):
        """startNewMining (:131-140): pick <= 2 uncles, compute difficulty
        and the 10ms success probability."""
        n, a = self.node_count, self.capacity
        ids = jnp.arange(n, dtype=jnp.int32)
        # Honest miners extend their head; a selfish miner keeps extending
        # its private chain until "they won" switches it back to the public
        # head (onMinedBlock :52 / onReceivedBlock :74-76).
        f = jnp.where(p.mine_private & (p.private_blk >= 0), p.private_blk,
                      p.head)
        hf = p.arena.height[jnp.maximum(f, 0)]

        # Ancestors anc[k] at height hf - k, k = 0..7, and their uncles
        # form the already-included set (possibleUncles :66-76).
        anc = [f]
        for _ in range(7):
            anc.append(jnp.where(anc[-1] >= 0,
                                 p.arena.parent[jnp.maximum(anc[-1], 0)], -1))
        anc_arr = jnp.stack(anc, axis=1)                    # [N, 8]
        inc = jnp.concatenate(
            [anc_arr,
             p.u1[jnp.maximum(anc_arr, 0)], p.u2[jnp.maximum(anc_arr, 0)]],
            axis=1)                                         # [N, 24]

        blocks = jnp.arange(a, dtype=jnp.int32)[None, :]    # [1, A]
        hb = p.arena.height[None, :]
        k = hf[:, None] - hb                                # level index
        anc_at = jnp.take_along_axis(anc_arr, jnp.clip(k, 0, 7), axis=1)
        sib = p.arena.parent[jnp.maximum(anc_at, 0)] == p.arena.parent
        # received bit per (node, block):
        word = p.received[:, (jnp.arange(a) // 32)]
        got = ((word >> (jnp.arange(a) % 32).astype(U32)) & U32(1)) != 0
        cand = (got & p.arena.valid[None, :] &
                (blocks < p.arena.n) & (blocks > 0) &
                (k >= 0) & (k <= 6) & sib &
                jnp.all(blocks[:, :, None] != inc[:, None, :], axis=2))

        # UncleCmp (:97-115): own uncles first (higher height first), then
        # others lowest height first.
        mine = p.arena.producer[None, :] == ids[:, None]
        big = jnp.int32(1 << 24)
        key = jnp.where(mine, (1 << 20) - hb + hf[:, None],
                        (1 << 21) + hb - hf[:, None] + 7)
        key = jnp.where(cand, key, big)
        u1 = jnp.argmin(key, axis=1).astype(jnp.int32)
        k1 = jnp.take_along_axis(key, u1[:, None], axis=1)[:, 0]
        key2 = jnp.where(jnp.arange(a)[None, :] == u1[:, None], big, key)
        u2 = jnp.argmin(key2, axis=1).astype(jnp.int32)
        k2 = jnp.take_along_axis(key2, u2[:, None], axis=1)[:, 0]
        u1 = jnp.where(k1 < big, u1, -1)
        u2 = jnp.where(k2 < big, u2, -1)

        # Constantinople difficulty (:283-296), scaled by 2^DIFF_SHIFT.
        fd = p.diff_s[jnp.maximum(f, 0)]
        gap = ((t - p.arena.time[jnp.maximum(f, 0)]) * self.tick_ms) // 9000
        all_d = difficulty_s(fd, hf, gap, p.u1[jnp.maximum(f, 0)] >= 0)

        # solveIn10ms (:225-231): 1 - (1-1/d)^(hp*2^30/100 per tick).
        thr = 1.0 - jnp.exp(-(p.hash_power.astype(jnp.float32) * (1 << 9)) /
                            (100.0 * all_d.astype(jnp.float32)))

        return p.replace(
            min_father=jnp.where(need, f, p.min_father),
            min_u1=jnp.where(need, u1, p.min_u1),
            min_u2=jnp.where(need, u2, p.min_u2),
            min_diff=jnp.where(need, all_d, p.min_diff),
            thr=jnp.where(need, thr, p.thr))

    # ---------------------------------------------------------------- step

    def step(self, p: PoWState, nodes, inbox, t, key):
        n, a, aw = self.node_count, self.capacity, self.aw
        ids = jnp.arange(n, dtype=jnp.int32)
        S = inbox.src.shape[1]
        alive = ~nodes.down

        # ---- receive blocks (onBlock :195-221 + strategy hooks) ----
        for s in range(S):
            ok = inbox.valid[:, s] & alive
            b = jnp.clip(inbox.data[:, s, 0], 0, a - 1)
            received, new = bc.receive_block(p.received, ids, b, ok)
            p = p.replace(received=received)
            old_head = p.head
            head = self._best(p, p.head, jnp.where(new, b, -1), ids)
            head_chg = new & (head != old_head)
            # switchMining is true for every shipped strategy: abort the
            # candidate on a new head, or when the block could improve our
            # uncle set (:203-216).
            uncle_hit = new & (p.min_father >= 0) & \
                self._possible_uncle_of(p, p.min_father, b)
            p = p.replace(
                head=head,
                min_father=jnp.where(head_chg | uncle_hit, -1,
                                     p.min_father))

            # onReceivedBlock — selfish strategies (:55-115 / S2 :55-80).
            selfish = new & (p.strategy > 0)
            oh = self._best(p, p.others_head, jnp.where(selfish, b, -1), ids)
            oh_chg = selfish & (oh != p.others_head) & (oh == b)
            p = p.replace(others_head=oh)
            priv_h = jnp.where(p.private_blk >= 0,
                               p.arena.height[jnp.maximum(p.private_blk, 0)],
                               0)
            rcv_h = p.arena.height[jnp.maximum(b, 0)]
            delta_p = priv_h - (rcv_h - 1)
            they_won_1 = oh_chg & (p.strategy == SELFISH) & (delta_p <= 0)
            they_won_2 = oh_chg & (p.strategy == SELFISH2) & (p.head == b)
            they_won = they_won_1 | they_won_2
            # release everything (sendAllMined) and mine on their head
            unsent, rel = self._release_chain(
                p, jnp.where(they_won, p.private_blk, -1), ids)
            p = p.replace(mined_unsent=unsent, release=rel,
                          mine_private=p.mine_private & ~they_won,
                          min_father=jnp.where(they_won, -1, p.min_father))

            ahead = oh_chg & ~they_won
            # SELFISH: deltaP 1/2 -> publish from private top; far
            # ahead -> walk down toward rcv height while parents are
            # still unsent, guard on total difficulty (:77-103).
            top = p.private_blk
            def walk_cond(st):
                cur, go = st
                par = p.arena.parent[jnp.maximum(cur, 0)]
                par_unsent = bitset.get_bit(p.mined_unsent,
                                            jnp.maximum(par, 0))
                return jnp.any(go & (cur >= 0) & par_unsent &
                               (p.arena.height[jnp.maximum(cur, 0)] >
                                rcv_h))

            def walk_body(st):
                cur, go = st
                par = p.arena.parent[jnp.maximum(cur, 0)]
                par_unsent = bitset.get_bit(p.mined_unsent,
                                            jnp.maximum(par, 0))
                step = go & (cur >= 0) & par_unsent & \
                    (p.arena.height[jnp.maximum(cur, 0)] > rcv_h)
                return jnp.where(step, par, cur), go

            walk_go = ahead & (p.strategy == SELFISH) & (delta_p > 2)
            top_w, _ = jax.lax.while_loop(walk_cond, walk_body,
                                          (top, walk_go))
            top = jnp.where(walk_go, top_w, top)
            # difficulty guard when heights still differ (:93-101)
            at_rcv = bc.walk_to_height(p.arena, top, rcv_h)
            guard_fail = (p.strategy == SELFISH) & (delta_p > 2) & \
                (p.arena.height[jnp.maximum(top, 0)] != rcv_h) & \
                _td_gt(p, b, at_rcv)
            # SELFISH2: walk while parent strictly beats rcv (:66-71)
            def w2_cond(st):
                cur, go = st
                par = p.arena.parent[jnp.maximum(cur, 0)]
                return jnp.any(go & (par >= 0) &
                               (p.arena.height[jnp.maximum(cur, 0)] >=
                                rcv_h) &
                               _td_gt(p, par, b))

            def w2_body(st):
                cur, go = st
                par = p.arena.parent[jnp.maximum(cur, 0)]
                step = go & (par >= 0) & \
                    (p.arena.height[jnp.maximum(cur, 0)] >= rcv_h) & \
                    _td_gt(p, par, b)
                return jnp.where(step, par, cur), go

            w2_go = ahead & (p.strategy == SELFISH2)
            top2, _ = jax.lax.while_loop(w2_cond, w2_body,
                                         (p.private_blk, w2_go))
            top = jnp.where(w2_go, top2, top)

            do_rel = ahead & ~guard_fail & (p.strategy != AGENT)
            unsent, rel = self._release_chain(
                p, jnp.where(do_rel, top, -1), ids)
            oh2 = self._best(p, p.others_head,
                             jnp.where(do_rel, top, -1), ids)
            p = p.replace(mined_unsent=unsent, release=rel,
                          others_head=oh2)

            # AGENT (ETHMinerAgent.onReceivedBlock :186-196): private blocks
            # at height <= the others' head can no longer win the race —
            # publish them (queued broadcasts drain one per tick).  Only
            # node 1 ever runs AGENT, so build the overtaken mask for that
            # row alone instead of an [N, A] sweep per inbox slot.
            if self.byz_strategy == AGENT:
                agent_rcv1 = new[1] & (p.strategy[1] == AGENT)
                oth_h2 = p.arena.height[jnp.maximum(p.others_head[1], 0)]
                over = (p.arena.height <= oth_h2).reshape(aw, 32)
                packed = jnp.sum(
                    over.astype(U32) << jnp.arange(32, dtype=U32)[None, :],
                    axis=1)
                over_bits = jnp.where(agent_rcv1,
                                      p.mined_unsent[1] & packed, U32(0))
                p = p.replace(
                    mined_unsent=p.mined_unsent.at[1].set(
                        p.mined_unsent[1] & ~over_bits),
                    release=p.release.at[1].set(p.release[1] | over_bits))

        # ---- mining tick (mine10ms :118-129) ----
        miner = alive & (p.hash_power > 0)
        need = miner & (p.min_father < 0)
        p = self._start_mining(p, need, t)
        u = prng.uniform_float(prng.hash3(p.seed, TAG_MINE, t), ids)
        found = miner & (p.min_father >= 0) & (u < p.thr)

        arena, blk = bc.alloc(p.arena, found, p.min_father, ids, t)
        bw = jnp.maximum(blk, 0)
        fw = jnp.maximum(p.min_father, 0)
        p = p.replace(
            arena=arena,
            diff_s=p.diff_s.at[
                jnp.where(found, blk, a)].set(p.min_diff, mode="drop"),
            td_hi=p.td_hi.at[jnp.where(found, blk, a)].set(
                p.td_hi[fw] + ((p.td_lo[fw] + p.min_diff) >> 30),
                mode="drop"),
            td_lo=p.td_lo.at[jnp.where(found, blk, a)].set(
                (p.td_lo[fw] + p.min_diff) & ((1 << 30) - 1),
                mode="drop"),
            u1=p.u1.at[jnp.where(found, blk, a)].set(p.min_u1, mode="drop"),
            u2=p.u2.at[jnp.where(found, blk, a)].set(p.min_u2, mode="drop"))

        received, _ = bc.receive_block(p.received, ids, blk, found)
        head = self._best(p.replace(received=received), p.head,
                          jnp.where(found, blk, -1), ids)
        p = p.replace(received=received, head=head,
                      min_father=jnp.where(found, -1, p.min_father))

        # honest: send at +1 tick (sendBlock :152-160); selfish: keep.
        hon_found = found & (p.strategy == HONEST)
        bit = jnp.where(hon_found[:, None], bitset.one_bit(bw, aw), U32(0))
        release = p.release | bit
        sel_found = found & (p.strategy > 0)
        mined_unsent = p.mined_unsent | jnp.where(
            sel_found[:, None], bitset.one_bit(bw, aw), U32(0))
        private_blk = jnp.where(sel_found, blk, p.private_blk)
        p = p.replace(release=release, mined_unsent=mined_unsent,
                      private_blk=private_blk,
                      mine_private=p.mine_private |
                      (sel_found & (p.strategy != AGENT)))

        # selfish onMinedBlock (:38-53): at deltaP == 0 with two own blocks
        # in a row, publish the private chain.  (The reference's deltaP
        # formula makes this trigger require others being two ahead of the
        # mining base at found-time — a rare race there and here; kept
        # formula-for-formula.)
        priv_h = jnp.where(p.private_blk >= 0,
                           p.arena.height[jnp.maximum(p.private_blk, 0)], 0)
        oth_h = p.arena.height[jnp.maximum(p.others_head, 0)]
        depth2 = self._depth(p, p.private_blk, ids) == 2
        pub = sel_found & (p.strategy != AGENT) & \
            (priv_h - (oth_h - 1) == 0) & depth2
        unsent, rel = self._release_chain(
            p, jnp.where(pub, p.private_blk, -1), ids)
        oh = self._best(p, p.others_head,
                        jnp.where(pub, p.private_blk, -1), ids)
        p = p.replace(mined_unsent=unsent, release=rel, others_head=oh)

        # ---- drain one queued broadcast per node per tick ----
        rel_any = jnp.any(p.release != 0, axis=1)
        word_has = p.release != 0
        first_word = jnp.argmax(word_has, axis=1).astype(jnp.int32)
        word = jnp.take_along_axis(p.release, first_word[:, None],
                                   axis=1)[:, 0]
        low = word & (~word + U32(1))
        bitpos = 31 - jax.lax.clz(jnp.maximum(low, U32(1)).astype(jnp.int32))
        send_blk = jnp.clip(first_word * 32 + bitpos, 0, a - 1)
        clear = bitset.one_bit(send_blk, aw)
        p = p.replace(release=jnp.where(rel_any[:, None],
                                        p.release & ~clear, p.release))

        out = empty_outbox(self.cfg).replace(
            bcast=rel_any,
            bcast_payload=send_blk[:, None].astype(jnp.int32),
            bcast_size=jnp.ones((n,), jnp.int32))
        return p, nodes, out

    def next_action_time(self, p: PoWState, nodes, t):
        """Quiet-window oracle half (core/protocol.py).  Mining is a
        FRESH per-tick Bernoulli draw keyed on t (mine10ms :118-129) —
        skipping a tick would drop a draw from the stream and change
        every subsequent block arrival, so any live miner pins every
        tick (a geometric-jump rewrite would be faster but not
        bit-identical; deliberately not done).  Only miner-free windows
        are skippable: observer-only configs, and the drain of queued
        block broadcasts after all miners go down — then block arrivals
        ride the engine's broadcast-oracle term alone."""
        from ..core.protocol import FAR_FUTURE
        mining = jnp.any((~nodes.down) & (p.hash_power > 0))
        queued = jnp.any(p.release != 0)
        return jnp.where(mining | queued, t, FAR_FUTURE).astype(jnp.int32)


# ------------------------------------------------------------- host stats

def rewards_by_miner(pstate, head: int, until_height: int = 0) -> dict:
    """allRewardsById (ETHPoW.java:219-230): walk the chain from `head`,
    2.0 per block + uncle rewards (rewards() :183-198)."""
    arena = bc.to_numpy(pstate.arena)
    u1 = np.asarray(pstate.u1)
    u2 = np.asarray(pstate.u2)
    out: dict = {}
    cur = int(head)
    while cur > 0 and arena["height"][cur] > until_height:
        prod = int(arena["producer"][cur])
        rwd = 2.0
        p_extra = 0.0
        for u in (int(u1[cur]), int(u2[cur])):
            if u >= 0:
                u_r = 2.0 * (arena["height"][u] + 8 - arena["height"][cur]) \
                    / 8
                out[int(arena["producer"][u])] = \
                    out.get(int(arena["producer"][u]), 0.0) + u_r
                p_extra += 2.0 / 32
        out[prod] = out.get(prod, 0.0) + rwd + p_extra
        cur = int(arena["parent"][cur])
    return out


def avg_difficulty(pstate, head: int, until_height: int = 0) -> float:
    """avgDifficulty (ETHPoW.java:232-239): mean raw difficulty over the
    chain from `head` down to (excluding) `until_height`."""
    arena = bc.to_numpy(pstate.arena)
    diff = np.asarray(pstate.diff_s, np.float64) * 2.0 ** DIFF_SHIFT
    tot, cnt, cur = 0.0, 0, int(head)
    while cur > 0 and arena["height"][cur] > until_height:
        tot += diff[cur]
        cnt += 1
        cur = int(arena["parent"][cur])
    return tot / max(1, cnt)


def try_miner(builder_name, nl_name, miner, pows, hours, runs,
              number_of_miners=10, tick_ms=10, chunk=2000, capacity=8192,
              **proto_kw):
    """Strategy-evaluation harness (ETHMiner.tryMiner, ETHMiner.java:234-308)
    reshaped for the TPU: all `runs` seeds execute as ONE vmapped batch
    instead of the reference's sequential loop.  `miner` is the strategy
    name ('ETHMiner', 'ETHSelfishMiner', ...).  Prints the reference's CSV
    header/rows and returns the rows as dicts."""
    from ..core.harness import run_multiple_times
    print("miner, hashrate ratio, revenue ratio, revenue, uncle rate, "
          "total revenue, avg difficulty")
    rows = []
    ticks = int(hours * 3600 * 1000) // tick_ms
    for pw in pows:
        proto = ETHPoW(number_of_miners=number_of_miners,
                       byz_class_name=miner, byz_mining_ratio=pw,
                       node_builder_name=builder_name,
                       network_latency_name=nl_name, tick_ms=tick_ms,
                       capacity=capacity, **proto_kw)
        res = run_multiple_times(
            proto, run_count=runs, max_time=ticks, chunk=chunk,
            first_seed=1, cont_if=lambda net, ps: jnp.asarray(True))
        rew1 = ur = diff = tot = 0.0
        for i in range(runs):
            ps = jax.tree_util.tree_map(lambda x: x[i], res.pstates)
            arena = bc.to_numpy(ps.arena)
            # Observer node 0's head is the PUBLIC consensus chain — a
            # selfish miner's own head may still include private blocks.
            base = int(np.asarray(ps.head)[0])
            # Skip warm-up and cool-down blocks on long runs (:255-263).
            skip = 5000 if hours > 30 else 0
            for _ in range(skip):
                par = int(arena["parent"][base])
                if par <= 0:
                    break
                base = par
            limit = GENESIS_HEIGHT + skip
            r = rewards_by_miner(ps, base, until_height=limit)
            rew1 += r.get(1, 0.0)
            tot += sum(r.values())
            ur += uncle_rate(ps, base, until_height=limit)
            diff += avg_difficulty(ps, base, until_height=limit)
        row = dict(miner=miner or "ETHMiner", pow=pw,
                   revenue_ratio=rew1 / max(tot, 1e-9),
                   revenue=rew1 / runs, uncle_rate=ur / runs,
                   total_revenue=tot / runs, avg_difficulty=diff / runs)
        rows.append(row)
        print(f"{row['miner']}/{nl_name}/{hours}/{runs}, {pw:.2f}, "
              f"{row['revenue_ratio']:.4f}, {row['revenue']:.0f}, "
              f"{row['uncle_rate']:.4f}, {row['total_revenue']:.0f}, "
              f"{row['avg_difficulty']:.0f}")
    return rows


class Decision:
    """ETHPoW.Decision (ETHPoW.java:350-375): a choice taken at
    `taken_at_height`, evaluated when the head reaches `reward_at_height`.
    `fields` land in the CSV row ahead of the reward."""

    def __init__(self, taken_at_height: int, reward_at_height: int,
                 fields=()):
        if reward_at_height <= taken_at_height:
            raise ValueError("reward height must be after the decision")
        self.taken_at_height = taken_at_height
        self.reward_at_height = reward_at_height
        self.fields = tuple(fields)

    def for_csv(self) -> str:
        return ",".join(str(f) for f in
                        (self.taken_at_height, self.reward_at_height)
                        + self.fields)

    def reward(self, pstate, head: int, miner_id: int = 1) -> float:
        """Default reward: the miner's rewards on the head chain above the
        decision height (Decision.reward :370-374)."""
        return rewards_by_miner(pstate, head,
                                until_height=self.taken_at_height
                                ).get(miner_id, 0.0)


class DecisionLog:
    """ETHAgentMiner's decision bookkeeping (ETHAgentMiner.java:16-66):
    decisions queue sorted by evaluation height; when the head passes one,
    its realized reward is appended to `decisions.csv`."""

    def __init__(self, path="decisions.csv", miner_id=1):
        self.path = path
        self.miner_id = miner_id
        self.pending: list = []

    def add(self, d: Decision):
        import bisect
        keys = [x.reward_at_height for x in self.pending]
        self.pending.insert(bisect.bisect_right(keys, d.reward_at_height), d)

    def on_new_head(self, pstate, head: int):
        arena_h = int(np.asarray(pstate.arena.height)[int(head)])
        out = []
        while self.pending and self.pending[0].reward_at_height <= arena_h:
            d = self.pending.pop(0)
            out.append(f"{d.for_csv()},{d.reward(pstate, head, self.miner_id)}")
        if out:
            with open(self.path, "a") as f:
                f.write("\n".join(out) + "\n")
        return out


class MinerAgentEnv:
    """ETHMinerAgent parity (ethpow/ETHMinerAgent.java): step-wise control
    of the byzantine miner for RL agents.  The reference needs a pyjnius
    JVM bridge (:11-36); here the framework IS Python, so the env drives
    the jitted simulation directly and reads state off the device.

    The byzantine miner (node 1) runs strategy AGENT: it never publishes on
    its own (sendMinedBlock -> false, :63-66) except for blocks already
    overtaken by the public chain (:186-196); the agent decides with
    `send_mined_blocks`."""

    ON_MINED_BLOCK = 1       # decisionNeeded codes (:50-53)
    ON_OTHER_NEW_HEAD = 2
    ON_OTHER_PRIVATE_HEAD = 3

    def __init__(self, byz_mining_ratio, seed=0, decision_log=None, **kw):
        kw.setdefault("network_latency_name", "NetworkFixedLatency(1000)")
        kw.setdefault("node_builder_name",
                      builders.registry_name("cities", True, 0.0))
        self.proto = ETHPoW(byz_class_name="ETHMinerAgent",
                            byz_mining_ratio=byz_mining_ratio, **kw)
        self.net, self.p = self.proto.init(seed)
        self.log = decision_log

    @classmethod
    def create(cls, byz_mining_ratio, seed=0):
        """ETHMinerAgent.create (:229-243)."""
        return cls(byz_mining_ratio, seed)

    # ------------------------------------------------------------- driving

    def _until_decision_fn(self):
        """One jitted device program: tick until decisionNeeded != 0
        (goNextStep :92-102) — the whole polling loop stays on-device
        instead of the reference's 1 ms Java round-trips."""
        import jax as _jax
        from ..core.network import step_ms
        proto = self.proto

        def go(net, p, budget):
            def cond(st):
                _, _, code, left = st
                return (code == 0) & (left > 0)

            def body(st):
                net, p, _, left = st
                h0, oh0 = p.head[1], p.others_head[1]
                mu0 = bitset.popcount(p.mined_unsent[1])
                net, p = step_ms(proto, net, p)
                mu1 = bitset.popcount(p.mined_unsent[1])
                h1 = p.head[1]
                others = p.arena.producer[jnp.maximum(h1, 0)] != 1
                code = jnp.where(
                    mu1 > mu0, self.ON_MINED_BLOCK,
                    jnp.where((mu1 > 0) & (h1 != h0) & others,
                              self.ON_OTHER_NEW_HEAD,
                              jnp.where((mu1 > 0) & (p.others_head[1] != oh0),
                                        self.ON_OTHER_PRIVATE_HEAD, 0)))
                return net, p, code, left - 1

            return _jax.lax.while_loop(
                cond, body, (net, p, jnp.int32(0), budget))

        return _jax.jit(go)

    def go_next_step(self, max_ticks=1_000_000) -> int:
        """Advance the simulation until the agent has a decision to take
        (goNextStep :92-102); returns the decision code (0 = budget hit)."""
        if not hasattr(self, "_go"):
            self._go = self._until_decision_fn()
        self.net, self.p, code, _ = self._go(self.net, self.p,
                                             jnp.int32(max_ticks))
        code = int(code)
        if self.log is not None:
            self.log.on_new_head(self.p, int(np.asarray(self.p.head)[1]))
        return code

    def _unsent_blocks(self):
        word = np.asarray(self.p.mined_unsent[1])
        t = np.asarray(self.p.arena.time)
        out = [b for b in range(self.proto.capacity)
               if word[b // 32] >> (b % 32) & 1]
        return sorted(out, key=lambda b: int(t[b]))      # oldest first

    def send_mined_blocks(self, how_many: int):
        """Publish the `how_many` oldest private blocks (sendMinedBlocks
        :68-90 + actionSendOldestBlockMined :215-221).

        The reference's loop is ``while (howMany-- > 0 &&
        !minedToSend.isEmpty())``: the POST-decrement means howMany ends
        at 0 — and the restart-on-head fires — only when the queue ran
        dry with exactly one request remaining (sent == howMany-1),
        never when the request was fully consumed (howMany ends -1).
        privateMinerBlock clears whenever the queue is empty afterwards,
        even if nothing was sent (:85-87).  actionSendOldestBlockMined
        (:219-226) also advances otherMinersHead to each sent block whose
        height exceeds it, so a publish immediately raises the baseline
        that getSecretAdvance measures against.  (The reference also
        gates the restart on ``inMining != null``; our miners are always
        mining between ticks, so that is always true here.)"""
        blocks = self._unsent_blocks()
        send = blocks[:how_many]
        aw = self.proto.aw
        p = self.p
        unsent = p.mined_unsent
        release = p.release
        heights = np.asarray(p.arena.height)
        oh0 = oh = int(np.asarray(p.others_head)[1])
        oh_h = int(heights[max(oh, 0)])
        for b in send:
            bit = bitset.one_bit(jnp.asarray(b, jnp.int32), aw)
            unsent = unsent.at[1].set(unsent[1] & ~bit)
            release = release.at[1].set(release[1] | bit)
            if int(heights[b]) > oh_h:
                oh, oh_h = b, int(heights[b])
        pb = int(np.asarray(p.private_blk)[1])
        restart = len(send) == how_many - 1 and pb >= 0
        queue_empty = len(blocks) <= how_many
        self.p = p.replace(
            mined_unsent=unsent, release=release,
            others_head=(p.others_head.at[1].set(oh) if oh != oh0
                         else p.others_head),
            private_blk=(p.private_blk.at[1].set(-1) if queue_empty
                         else p.private_blk),
            min_father=(p.min_father.at[1].set(-1) if restart
                        else p.min_father))

    # ---------------------------------------------------------- observables

    def _walk_run(self, want_mine: bool) -> int:
        arena = bc.to_numpy(self.p.arena)
        cur = int(np.asarray(self.p.head)[1])
        score = 0
        while cur > 0 and (int(arena["producer"][cur]) == 1) == want_mine:
            cur = int(arena["parent"][cur])
            score += 1
        return score

    def get_advance(self) -> int:
        """Own blocks in a row from the head (:111-119)."""
        return self._walk_run(True)

    def get_lag(self) -> int:
        """Others' blocks in a row from the head (:121-129)."""
        return self._walk_run(False)

    def get_secret_advance(self) -> int:
        """Private-chain height advance over the public head (:103-108)."""
        p = self.p
        pb = int(np.asarray(p.private_blk)[1])
        priv = 0 if pb < 0 else int(np.asarray(p.arena.height)[pb])
        oth = int(np.asarray(p.arena.height)[
            int(np.asarray(p.others_head)[1])])
        return max(0, priv - oth)

    def get_reward(self, last_blocks_count=None) -> float:
        head = int(np.asarray(self.p.head)[1])
        until = 0
        if last_blocks_count is not None:
            until = int(np.asarray(self.p.arena.height)[head]) - \
                last_blocks_count
        return rewards_by_miner(self.p, head,
                                until_height=until).get(1, 0.0)

    def get_reward_ratio(self) -> float:
        head = int(np.asarray(self.p.head)[1])
        r = rewards_by_miner(self.p, head)
        tot = sum(r.values())
        return r.get(1, 0.0) / tot if tot > 0 else 0.0

    def i_am_ahead(self) -> bool:
        head = int(np.asarray(self.p.head)[1])
        return int(np.asarray(self.p.arena.producer)[head]) == 1

    def count_my_blocks(self) -> int:
        arena = bc.to_numpy(self.p.arena)
        cur = int(np.asarray(self.p.head)[1])
        count = 0
        while cur > 0:
            count += int(arena["producer"][cur]) == 1
            cur = int(arena["parent"][cur])
        return count

    def get_time_in_seconds(self) -> int:
        """ETHPowWithAgent.getTimeInSeconds (:225-227)."""
        return int(np.asarray(self.net.time)) * self.proto.tick_ms // 1000


def uncle_rate(pstate, head: int, until_height: int = 0) -> float:
    """uncleRate (ETHPoW.java:241-252): uncles / (uncles + head.height -
    first.height), walking down to (excluding) until_height."""
    arena = bc.to_numpy(pstate.arena)
    u1 = np.asarray(pstate.u1)
    u2 = np.asarray(pstate.u2)
    uncles, cur, first = 0, int(head), None
    head_h = int(arena["height"][int(head)])
    while cur > 0 and arena["height"][cur] > until_height:
        uncles += int(u1[cur] >= 0) + int(u2[cur] >= 0)
        first = cur
        cur = int(arena["parent"][cur])
    if first is None:
        return 0.0
    return uncles / max(1, uncles + head_h - int(arena["height"][first]))
