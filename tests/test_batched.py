"""Batch-folded engine (core/batched.py) — bit-equality with the
vmapped per-seed scan.

The folded path exists purely as a lowering workaround (the vmapped
mailbox scatter serializes per seed on TPU, reports/PROFILE_r4.md), so
its results must be EXACTLY the vmapped path's across the full pytree.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wittgenstein_tpu.core.batched import scan_chunk_batched
from wittgenstein_tpu.core.network import scan_chunk
from wittgenstein_tpu.models.handel import Handel


def _trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _run_both(proto, ms, seeds=3, plane_barrier=True):
    ref = jax.jit(jax.vmap(scan_chunk(proto, ms, superstep=2)))
    bat = jax.jit(scan_chunk_batched(proto, ms,
                                     plane_barrier=plane_barrier))
    sd = jnp.arange(seeds, dtype=jnp.int32)
    nets, ps = jax.vmap(proto.init)(sd)
    out_ref = ref(nets, ps)
    nets, ps = jax.vmap(proto.init)(sd)
    out_bat = bat(nets, ps)
    return out_ref, out_bat


def test_batched_matches_vmapped_plain():
    proto = Handel(node_count=64, threshold=56, nodes_down=6,
                   pairing_time=4, dissemination_period_ms=20,
                   level_wait_time=50, fast_path=10)
    a, b = _run_both(proto, 80)
    _trees_equal(a, b)
    _, ps = b
    assert int(np.asarray(ps.sigs_checked).sum()) > 0


_HANDEL = dict(node_count=64, threshold=56, nodes_down=6, pairing_time=4,
               dissemination_period_ms=20, level_wait_time=50,
               fast_path=10)
#: pairing 3, period 10: lcm 30, so the block is no power of two
_ODD_LCM = dict(node_count=64, threshold=60, nodes_down=0, pairing_time=3,
                dissemination_period_ms=10, level_wait_time=30,
                fast_path=4)

#: (Handel parameters, entry residue t0_mod, chunk: a multiple of the
#: schedule block, past the first level wait so that later levels'
#: periodic dissemination and verification are compared too).  At
#: residue 10 periodic dissemination falls in the merged rest (lead:
#: no verify, no periodic), not in the lead.
PHASE_CASES = {
    "exact": (_HANDEL, 0, 120),
    "cardinal": ({**_HANDEL, "mode": "cardinal"}, 0, 80),
    "cardinal-residue10": ({**_HANDEL, "mode": "cardinal"}, 10, 80),
    "cardinal-lcm30": ({**_ODD_LCM, "mode": "cardinal"}, 0, 60),
}


@pytest.mark.parametrize("case", sorted(PHASE_CASES))
def test_batched_matches_vmapped_phase_specialized(case):
    """The compact phase-specialized batched scan (one lead superstep
    and an inner scan of the OR-merged rest per schedule block) is
    bit-identical to the plain batched scan and to the vmapped
    `scan_chunk(t0_mod)`, whose block is fully unrolled — entered at
    ``time % lcm == t0_mod`` over two to six blocks."""
    params, t0_mod, ms = PHASE_CASES[case]
    proto = Handel(**params)
    assert ms % proto.schedule_lcm == 0 and ms > params["level_wait_time"]
    nets, ps = jax.vmap(proto.init)(jnp.arange(2, dtype=jnp.int32))
    if t0_mod:
        nets, ps = jax.jit(scan_chunk_batched(proto, t0_mod))(nets, ps)
    plain = jax.jit(scan_chunk_batched(proto, ms))(nets, ps)
    spec = jax.jit(scan_chunk_batched(proto, ms, t0_mod=t0_mod))(nets, ps)
    unrolled = jax.jit(jax.vmap(scan_chunk(proto, ms, t0_mod=t0_mod,
                                           superstep=2)))(nets, ps)
    _trees_equal(plain, spec)
    _trees_equal(unrolled, spec)
    assert int(np.asarray(spec[1].sigs_checked).sum()) > 0


def test_phase_plan_lead_and_merged_rest():
    from wittgenstein_tpu.core.network import phase_plan
    proto = Handel(**_HANDEL)
    no = {"verify": False, "periodic": False}
    plan = phase_plan(proto, 0, 2)
    assert plan.block == 20 and plan.repeats == 9
    # t = 0, 1: only t = 1 verifies and disseminates periodically
    assert plan.lead == (no, {"verify": True, "periodic": True})
    # the odd ms of the rest keep verify (t = 5, 9, ...), not periodic
    assert plan.rest == (no, {"verify": True, "periodic": False})
    # residue 10: t = 10, 11 do neither; t = 21 (slot 1 of the 6th
    # superstep) disseminates, so the merged rest keeps both there
    plan = phase_plan(proto, 10, 2)
    assert plan.lead == (no, no)
    assert plan.rest == (no, {"verify": True, "periodic": True})
    # K not dividing the lcm: the block is lcm(20, 8) = 40
    plan = phase_plan(proto, 0, 8)
    assert (plan.block, plan.repeats, len(plan.lead)) == (40, 4, 8)
    # t = 21 is slot 5 of the third superstep
    assert plan.rest[5] == {"verify": True, "periodic": True}
    assert plan.rest[1] == {"verify": True, "periodic": False}
    # a key one hint lacks is kept dynamic (True) in the merge
    proto.phase_hints = lambda tmod: {} if tmod == 3 else \
        {"verify": False}
    assert phase_plan(proto, 0, 2).rest == ({"verify": False},
                                            {"verify": True})
    assert phase_plan(Handel(**_HANDEL, desynchronized_start=17), 0,
                      2) is None


def test_bin_order_packed_key_matches_two_keys():
    """The bin's one packed (rel, dest) key sorts exactly as the two
    keys it stands for: same stable order, invalid entries last in
    their own order, same group starts."""
    from wittgenstein_tpu.core.batched import _bin_order
    rng = np.random.default_rng(7)
    n = 13
    rel = jnp.asarray(rng.integers(1, 9, (3, 200)), jnp.int32)
    dest = jnp.asarray(rng.integers(0, n, (3, 200)), jnp.int32)
    valid = jnp.asarray(rng.random((3, 200)) < 0.8)
    two = _bin_order(rel, dest, valid)
    one = _bin_order(rel, dest, valid, pack_n=n)
    _trees_equal(two, one)
    ref = np.stack([np.lexsort((np.where(v, d, 2**31 - 1),
                                np.where(v, r, 2**31 - 1)))
                    for r, d, v in zip(np.asarray(rel), np.asarray(dest),
                                       np.asarray(valid))])
    np.testing.assert_array_equal(np.asarray(one[0]), ref)


def test_batched_box_split():
    import dataclasses
    proto = Handel(node_count=64, threshold=56, nodes_down=6,
                   pairing_time=4, dissemination_period_ms=20,
                   fast_path=10)
    proto.cfg = dataclasses.replace(proto.cfg, box_split=2)
    a, b = _run_both(proto, 80)
    _trees_equal(a, b)


@pytest.mark.parametrize("plane_barrier", [True, False])
def test_plane_barrier_bit_identity(plane_barrier):
    """The plane-ordering barrier in step_2ms_batched is ordering-only:
    results are bit-identical to the vmapped per-seed reference with the
    barrier on OR off (the barrier only changes whether XLA can update
    the ring planes in place).  This is the CPU evidence the
    core/batched.py docstring cites — plane_barrier=False was previously
    only exercised inside the TPU-only tools/ab_plane_barrier.py
    (ADVICE.md r5 item 1)."""
    proto = Handel(node_count=64, threshold=56, nodes_down=6,
                   pairing_time=4, dissemination_period_ms=20,
                   level_wait_time=50, fast_path=10)
    # ms=8: a few step_2ms_batched iterations
    a, b = _run_both(proto, 8, plane_barrier=plane_barrier)
    _trees_equal(a, b)


@pytest.mark.parametrize("plane_barrier", [True, False])
def test_step_2ms_batched_direct_iterations(plane_barrier):
    """ADVICE r5 item 1, at the exact granularity it asked for: a few
    DIRECT `step_2ms_batched` iterations (no scan wrapper) compared
    against the vmapped `step_kms(K=2)` reference, full-pytree equality
    asserted after EVERY iteration, with the barrier on and off."""
    from wittgenstein_tpu.core.batched import step_2ms_batched
    from wittgenstein_tpu.core.network import step_kms

    proto = Handel(node_count=64, threshold=56, nodes_down=6,
                   pairing_time=4, dissemination_period_ms=20,
                   level_wait_time=50, fast_path=10)

    @jax.jit
    def adv_batched(nets, ps):
        return step_2ms_batched(proto, nets, ps,
                                plane_barrier=plane_barrier)

    @jax.jit
    def adv_ref(nets, ps):
        return jax.vmap(lambda n_, p_: step_kms(proto, n_, p_, 2))(
            nets, ps)

    sd = jnp.arange(3, dtype=jnp.int32)
    nets_b, ps_b = jax.vmap(proto.init)(sd)
    nets_r, ps_r = jax.vmap(proto.init)(sd)
    for _ in range(4):
        nets_b, ps_b = adv_batched(nets_b, ps_b)
        nets_r, ps_r = adv_ref(nets_r, ps_r)
        _trees_equal((nets_r, ps_r), (nets_b, ps_b))


def test_batched_rejects_broadcast_protocols():
    from wittgenstein_tpu.models.pingpong import PingPong
    with pytest.raises(ValueError, match="broadcast-free"):
        scan_chunk_batched(PingPong(node_count=64), 40)


@pytest.mark.slow      # tier-1 budget (reports/TIER1_DURATIONS.md):
# 32 s; pallas-in-engine equality stays via test_gsf_pallas_merge_bit_equal
def test_batched_with_pallas_merge():
    """The batched engine composed with the fused Pallas delivery-merge
    kernel — the exact combination the on-chip bench session runs
    (WTPU_PALLAS=1 with the batched default) — stays bit-identical to
    the batched XLA-merge path."""
    kw = dict(node_count=64, threshold=56, nodes_down=6,
              pairing_time=4, dissemination_period_ms=20,
              level_wait_time=50, fast_path=10)
    ref_x, bat_x = _run_both(Handel(pallas_merge=False, **kw), 80)
    ref_p, bat_p = _run_both(Handel(pallas_merge=True, **kw), 80)
    _trees_equal(bat_x, bat_p)          # batched: kernel == XLA merge
    _trees_equal(ref_x, bat_p)          # == the vmapped XLA reference
    _trees_equal(ref_p, bat_p)          # == the vmapped kernel path
