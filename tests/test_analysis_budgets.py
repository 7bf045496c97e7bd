"""The round-5 carry-copy fix as a permanent tier-1 regression gate.

Round 5 cut the batched Handel superstep's scan-carry plane copies from
40 to 2 per while body (~31% of step time, reports/PROFILE_r4.md) by
adding the plane-ordering barrier in core/batched.py.  CPU HLO shows
the same copy-insertion decisions as TPU, so this compiles the pinned
small Handel analysis target and asserts the while-body plane-copy
count never climbs back above 2 — and that the checked-in budget file
actually encodes that gate (deleting the budget entry must fail here,
not silently stop gating).
"""

import json

from wittgenstein_tpu.analysis import framework, rules_carry
from wittgenstein_tpu.analysis.targets import get_target


def test_handel_while_body_plane_copies_le_2():
    target = get_target("Handel")
    from wittgenstein_tpu.analysis import hlo
    assert hlo.scan_bodies(target.hlo_text), (
        "no scan-shaped while body found in the compiled Handel "
        "superstep — the HLO parser matched nothing, so the plane-copy "
        "gate would pass vacuously (HLO text format change?)")
    metrics = rules_carry.measure(target)
    assert metrics["plane_copies"] <= 2, (
        f"Handel's compiled superstep copies {metrics['plane_copies']} "
        "mailbox ring planes per scan iteration (round-5 fixed state: 2)."
        " XLA's copy-insertion can no longer prove the scatters run in "
        "place — did the plane-ordering barrier in core/batched.py move "
        "or lose an operand? Run `python tools/carry_audit.py` for the "
        "per-leaf attribution.")


def test_phase_specialized_plane_copies_per_body_le_2():
    """The request plane's phase-specialized Handel program runs two
    scan bodies (the block with its lead superstep, and the inner scan
    of the merged rest); each copies no more ring planes than the
    plain program's one body."""
    target = get_target("Handel+phase")
    from wittgenstein_tpu.analysis import hlo
    assert len(hlo.scan_bodies(target.hlo_text)) == 2
    metrics = rules_carry.measure(target)
    assert metrics["body_plane_copies"] <= 2
    assert metrics["body_boxcount_copies"] <= 2


def test_checked_in_budget_encodes_the_gate():
    with open(framework.BUDGETS_PATH) as f:
        budgets = json.load(f)
    assert budgets["carry_copy"]["Handel"]["plane_copies"] <= 2
    plain = budgets["carry_copy"]["Handel"]
    phase = budgets["carry_copy"]["Handel+phase"]
    for m in ("body_plane_copies", "body_boxcount_copies"):
        assert phase[m] <= plain[m] <= 2
