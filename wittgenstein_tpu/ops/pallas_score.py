"""Fused Pallas verification-scoring kernel — the W-wide per-queue-entry
work of Handel's `bestToVerify` tick (`models/handel._pick_verification`:
sizeIfIncluded Handel.java:545-552 + the score Handel.java:651-664) in
one pass.

The XLA form materializes four [M, Q, W] intermediates per verify tick
(level range mask, and the masked total/verified/aggregate views) plus
the merged candidates — ~6 full passes over the queue's sig plane in
HBM.  The kernel reads each node block's sig rows and three bitset rows
once, builds the level mask in-register from (id, level) arithmetic
(`_levels.sibling_base` + `ops.bitset.range_mask` semantics), and emits
only the four [M, Q] summaries the rest of the tick consumes:

  s_inc     = popcount(merged | ver_e)   (merged = sig|inc_e if disjoint
                                          from inc_e else sig)
  pc_sig    = popcount(sig)
  pc_sv     = popcount(sig | ver_e)
  inter_agg = intersects(sig, agg_e)

Bit-equality with the XLA path is tested in tests/test_pallas_score.py
and end-to-end via the pallas_merge=True Handel runs (both kernels ride
the same switch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

I32 = jnp.int32
U32 = jnp.uint32


def _popcount_u32(v):
    """Bit-trick popcount (some Mosaic versions lack
    lax.population_count — tools/pallas_probe.py validates this form
    on the real toolchain)."""
    v = v - ((v >> 1) & U32(0x55555555))
    v = (v & U32(0x33333333)) + ((v >> 2) & U32(0x33333333))
    return ((((v + (v >> 4)) & U32(0x0F0F0F0F)) * U32(0x01010101))
            >> 24).astype(I32)


def _score_kernel(sig_ref, lvl_ref, ids_ref, inc_ref, ver_ref, agg_ref,
                  sinc_ref, psig_ref, psv_ref, iagg_ref, *, q_cap, w):
    blk = lvl_ref.shape[0]
    ids = ids_ref[...]                                  # [blk, 1]
    inc = inc_ref[...]                                  # [blk, W]
    ver = ver_ref[...]
    agg = agg_ref[...]
    wlo = jax.lax.broadcasted_iota(I32, (blk, w), 1) * 32

    s_inc, p_sig, p_sv, i_agg = [], [], [], []
    for q in range(q_cap):
        emask = _emask_for(ids, lvl_ref[:, q:q + 1], wlo)   # [blk, W]
        sig = sig_ref[:, q, :]                          # [blk, W]
        inc_e = inc & emask
        ver_e = ver & emask
        agg_e = agg & emask
        disj = jnp.sum(jnp.where((sig & inc_e) != 0, 1, 0), axis=1,
                       keepdims=True) == 0              # [blk, 1]
        merged = jnp.where(disj, sig | inc_e, sig)
        s_inc.append(jnp.sum(_popcount_u32(merged | ver_e), axis=1,
                             keepdims=True))
        p_sig.append(jnp.sum(_popcount_u32(sig), axis=1, keepdims=True))
        p_sv.append(jnp.sum(_popcount_u32(sig | ver_e), axis=1,
                            keepdims=True))
        i_agg.append(jnp.sum(jnp.where((sig & agg_e) != 0, 1, 0),
                             axis=1, keepdims=True))
    sinc_ref[...] = jnp.concatenate(s_inc, axis=1)
    psig_ref[...] = jnp.concatenate(p_sig, axis=1)
    psv_ref[...] = jnp.concatenate(p_sv, axis=1)
    iagg_ref[...] = jnp.concatenate(i_agg, axis=1)


def _emask_for(ids, lvl, wlo):
    """In-register level range mask — shared by both scoring kernels
    (the `_levels.sibling_base` + `ops.bitset.range_mask` arithmetic)."""
    half = jnp.where(lvl > 0, jnp.int32(1) << jnp.clip(lvl - 1, 0, 30), 0)
    half_nz = jnp.maximum(half, 1)
    mine = ids & ~(2 * half_nz - 1)
    base = mine + jnp.where((ids & half_nz) != 0, 0, half_nz)
    base = jnp.where(half > 0, base, 0)
    lo = jnp.clip(base - wlo, 0, 32)
    hi = jnp.clip(base + half - wlo, 0, 32)
    full = U32(0xFFFFFFFF)
    m_hi = jnp.where(hi >= 32, full, (U32(1) << hi.astype(U32)) - U32(1))
    m_lo = jnp.where(lo >= 32, full, (U32(1) << lo.astype(U32)) - U32(1))
    return m_hi & ~m_lo


def _gsf_score_kernel(sig_ref, lvl_ref, ids_ref, ver_ref, ind_ref,
                      vlc_ref, cs_ref, iv_ref, pwi_ref, pwv_ref, ii_ref,
                      *, q_cap, w):
    """GSF evaluateSig summaries (GSFSignature.java:482-580): per queue
    entry, the popcounts/intersections its score formula consumes."""
    blk = lvl_ref.shape[0]
    ids = ids_ref[...]
    ver = ver_ref[...]
    ind = ind_ref[...]
    wlo = jax.lax.broadcasted_iota(I32, (blk, w), 1) * 32

    vlc, cs, iv, pwi, pwv, ii = [], [], [], [], [], []
    for q in range(q_cap):
        emask = _emask_for(ids, lvl_ref[:, q:q + 1], wlo)
        sig = sig_ref[:, q, :]
        ver_l = ver & emask
        indiv_l = ind & emask
        with_indiv = indiv_l | sig
        vlc.append(jnp.sum(_popcount_u32(ver_l), axis=1, keepdims=True))
        cs.append(jnp.sum(_popcount_u32(sig), axis=1, keepdims=True))
        iv.append(jnp.sum(jnp.where((sig & ver_l) != 0, 1, 0), axis=1,
                          keepdims=True))
        pwi.append(jnp.sum(_popcount_u32(with_indiv), axis=1,
                           keepdims=True))
        pwv.append(jnp.sum(_popcount_u32(with_indiv | ver_l), axis=1,
                           keepdims=True))
        ii.append(jnp.sum(jnp.where((sig & indiv_l) != 0, 1, 0), axis=1,
                          keepdims=True))
    for ref, parts in ((vlc_ref, vlc), (cs_ref, cs), (iv_ref, iv),
                       (pwi_ref, pwi), (pwv_ref, pwv), (ii_ref, ii)):
        ref[...] = jnp.concatenate(parts, axis=1)


def score_row_bytes(q_cap: int, w: int) -> int:
    """Per-row VMEM cost model shared by both scoring kernels: q
    unrolled rounds x ~12 live [blk, W]-lane temporaries (masks, masked
    views, popcount intermediates) x 4 B.  The '12 live temporaries'
    constant is extrapolated from the merge kernel's on-chip observation
    (ADVICE.md r5 item 2) — not yet validated on the chip;
    the analysis vmem_budget rule holds launch configs to this model
    either way."""
    from .pallas_merge import _pad_lanes

    return q_cap * 12 * _pad_lanes(w) * 4


def _launch_scoring(kernel_fn, n_outputs, q_sig, q_lvl, ids,
                    *bitsets, interpret):
    """Shared pallas_call scaffolding for the per-entry scoring kernels:
    node-block grid over [M, ...] operands (q_sig [M, Q, W], q_lvl
    [M, Q], ids [M, 1], then the [M, W] bitset rows), `n_outputs`
    [M, Q] i32 outputs."""
    from jax.experimental import pallas as pl

    from .pallas_merge import _pick_block

    m, q, w = q_sig.shape
    blk = _pick_block(m, score_row_bytes(q, w))

    def spec(shape):
        return pl.BlockSpec((blk,) + shape,
                            lambda g: (g,) + (0,) * len(shape))

    kernel = functools.partial(kernel_fn, q_cap=q, w=w)
    return pl.pallas_call(
        kernel,
        grid=(m // blk,),
        in_specs=[spec((q, w)), spec((q,)), spec((1,))] +
                 [spec((w,))] * len(bitsets),
        out_specs=[spec((q,))] * n_outputs,
        out_shape=tuple(jax.ShapeDtypeStruct((m, q), I32)
                        for _ in range(n_outputs)),
        interpret=interpret,
    )(q_sig, q_lvl, ids.reshape(m, 1), *bitsets)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gsf_score_pallas(q_sig, q_lvl, ids, verified, ver_indiv,
                     interpret: bool = False):
    """GSF per-entry score inputs.  Returns (ver_l_card, card_sig,
    inter_verl (bool), pc_with_indiv, pc_with_indiv_or_verl,
    inter_indivl (bool)), each [M, Q] — bit-identical to the XLA block
    in `models/gsf._pick_verification`."""
    vlc, cs, iv, pwi, pwv, ii = _launch_scoring(
        _gsf_score_kernel, 6, q_sig, q_lvl, ids, verified, ver_indiv,
        interpret=interpret)
    return vlc, cs, iv != 0, pwi, pwv, ii != 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def score_queue_pallas(q_sig, q_lvl, ids, total_inc, ver_ind, last_agg,
                       interpret: bool = False):
    """Per-entry verification scores.  Shapes: q_sig [M, Q, W], q_lvl
    [M, Q], ids [M] (global node ids), bitsets [M, W].  Returns
    (s_inc, pc_sig, pc_sig_ver [M, Q] i32, inter_agg [M, Q] bool) —
    bit-identical to the `_pick_verification` per-piece XLA block.
    """
    s_inc, pc_sig, pc_sv, i_agg = _launch_scoring(
        _score_kernel, 4, q_sig, q_lvl, ids, total_inc, ver_ind,
        last_agg, interpret=interpret)
    return s_inc, pc_sig, pc_sv, i_agg != 0
