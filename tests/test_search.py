"""Adaptive boundary search (matrix/search.py): spec digests, the
bisection automaton, ground truth vs the exhaustive grid, the fleet
memo-table seam, and (slow) the checked-in boundary question's
probe-savings + determinism pins.

Fast tests drive a 6-step loss ladder (one compile key, ledger-joined
where possible); the slow battery runs the checked-in
tools/specs/search_loss_boundary.json question cold, warm and as a
2-worker fleet.
"""

import copy
import importlib.util
import json
import pathlib
import threading

import pytest

import wittgenstein_tpu.models  # noqa: F401 — fills the registry
from wittgenstein_tpu.matrix import (SearchReport, SearchSpec, SweepGrid,
                                     compile_search, plan, run_grid,
                                     run_search)
from wittgenstein_tpu.matrix.search import (_SliceState,
                                            exhaustive_boundaries)
from wittgenstein_tpu.serve import Scheduler

SPEC_PATH = pathlib.Path(__file__).parent.parent / "tools" / "specs" \
    / "search_loss_boundary.json"


def _loss_axis(n, step=20):
    return {"name": "loss", "field": "fault_schedule",
            "values": [{"loss": [[40, 160, p, 0, 32, 0, 32]]}
                       for p in range(0, n * step, step)],
            "labels": ["p%03d" % p for p in range(0, n * step, step)]}


def _spec(**kw):
    base = dict(
        name="t-search",
        grid={"name": "t-grid",
              "base": {"protocol": "PingPong",
                       "params": {"node_count": 32}, "seeds": [0],
                       "sim_ms": 160, "chunk_ms": 40,
                       "obs": ["metrics", "audit"],
                       "latency_model": "NetworkFixedLatency(50)"},
              "axes": [_loss_axis(6)]},
        axis="loss",
        predicate={"field": "summary.done_frac", "op": ">=",
                   "value": 0.99},
        coarse=2)
    base.update(kw)
    return SearchSpec.from_json(base)


def _cli():
    path = pathlib.Path(__file__).parent.parent / "tools" / "search.py"
    spec = importlib.util.spec_from_file_location("search_cli", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------- spec


def test_spec_roundtrip_and_digest_stability():
    s = _spec()
    again = SearchSpec.from_json(json.loads(s.canonical_json()))
    assert again == s
    assert again.digest() == s.digest()
    # dict ordering never moves the digest
    shuffled = SearchSpec.from_json(
        json.loads(json.dumps(s.to_json(), sort_keys=True)))
    assert shuffled.digest() == s.digest()


def test_spec_digest_sensitivity():
    """Every part of the question moves the digest — the probe
    sequence is a pure function of it, so nothing may alias."""
    s = _spec()
    two_axes = _spec(grid={
        "name": "t-grid", "base": s.grid.base,
        "axes": [_loss_axis(6),
                 {"name": "seed", "field": "seeds",
                  "values": [[0], [1]]}]})
    digests = {
        s.digest(),
        _spec(name="other").digest(),
        _spec(coarse=3).digest(),
        _spec(predicate={"field": "summary.done_frac", "op": ">=",
                         "value": 0.5}).digest(),
        _spec(predicate={"field": "summary.done_frac", "op": "<",
                         "value": 0.99}).digest(),
        _spec(predicate={"field": "time_to_done_ms", "op": "<=",
                         "value": 120}).digest(),
        _spec(grid={"name": "t-grid", "base": s.grid.base,
                    "axes": [_loss_axis(8)]}).digest(),
        two_axes.digest(),
        SearchSpec.from_json(dict(two_axes.to_json(),
                                  axis="seed")).digest(),
    }
    assert len(digests) == 9, \
        "a question change failed to move the search digest"


def test_spec_validation_refuses_with_remedy():
    with pytest.raises(ValueError, match="unknown key"):
        SearchSpec.from_json({"grid": {}, "axis": "a",
                              "predicate": {}, "bogus": 1})
    with pytest.raises(ValueError, match="missing required"):
        SearchSpec.from_json({"axis": "loss"})
    s = _spec()
    with pytest.raises(ValueError, match="not one of the grid's axes"):
        SearchSpec.from_json(dict(s.to_json(), axis="nope"))
    with pytest.raises(ValueError, match="exactly"):
        SearchSpec.from_json(dict(s.to_json(),
                                  predicate={"field": "x"}))
    with pytest.raises(ValueError, match="op"):
        SearchSpec.from_json(dict(
            s.to_json(), predicate={"field": "summary.done_frac",
                                    "op": "==", "value": 1}))
    with pytest.raises(ValueError, match="must be a number"):
        SearchSpec.from_json(dict(
            s.to_json(), predicate={"field": "summary.done_frac",
                                    "op": ">=", "value": True}))
    with pytest.raises(ValueError, match="field"):
        SearchSpec.from_json(dict(
            s.to_json(), predicate={"field": "wall_s", "op": ">=",
                                    "value": 1}))
    with pytest.raises(ValueError, match="coarse"):
        SearchSpec.from_json(dict(s.to_json(), coarse=1))
    with pytest.raises(ValueError, match="exhaustive sweep"):
        SearchSpec.from_json(dict(s.to_json(), coarse=7))
    with pytest.raises(ValueError, match="at least 2"):
        SearchSpec.from_json(dict(s.to_json(),
                                  grid={"name": "g",
                                        "base": s.grid.base,
                                        "axes": [_loss_axis(1)]}))
    with pytest.raises(ValueError, match="exclusion"):
        two = {"name": "g", "base": s.grid.base,
               "axes": [_loss_axis(2),
                        {"name": "seed", "field": "seeds",
                         "values": [[0], [1]]}],
               "exclude": [{"loss": "p000", "seed": "0"}]}
        SearchSpec.from_json(dict(s.to_json(), grid=two))
    with pytest.raises(ValueError, match="schema"):
        SearchSpec.from_json(dict(s.to_json(), schema=2))


# ------------------------------------------------------------ automaton


def _drive(n, coarse_idx, oracle):
    """Run the bisection automaton against a synthetic verdict oracle
    (no simulation): returns (probe index sequence, final state)."""
    sl = type("S", (), {"id": "*", "labels": {},
                        "cell_ids": tuple(f"c{i}" for i in range(n))})
    st = _SliceState(sl, coarse_idx)
    seq = []
    while True:
        nxt = st.next_probes()
        if not nxt:
            return seq, st
        for i in nxt:
            seq.append(i)
            st.observe(i, oracle(i), float(oracle(i)), None)


def test_bisection_probe_sequence_and_boundary():
    """The automaton's probe sequence is a pure function of the
    verdicts; its boundary equals the linear scan's first flip."""
    seq, st = _drive(16, (0, 5, 10, 15), lambda i: i < 7)
    assert seq == [0, 5, 10, 15, 7, 6]
    assert st.status == "boundary" and st.boundary_idx == 7
    # same oracle, same sequence — determinism is structural
    seq2, _ = _drive(16, (0, 5, 10, 15), lambda i: i < 7)
    assert seq2 == seq
    # every flip point agrees with the exhaustive linear scan
    for flip in range(1, 16):
        _, st = _drive(16, (0, 5, 10, 15), lambda i, f=flip: i < f)
        truth = next(i for i in range(16) if not (i < flip))
        assert st.boundary_idx == truth, f"flip at {flip}"


def test_bisection_edge_verdicts():
    _, st = _drive(8, (0, 7), lambda i: True)
    assert st.status == "all_pass" and st.boundary_idx is None
    _, st = _drive(8, (0, 7), lambda i: False)
    assert st.status == "all_fail"
    # >1 coarse flip: tagged divergent (the CLI's exit-1 story) but
    # still deterministically refines the FIRST bracket
    _, st = _drive(16, (0, 5, 10, 15),
                   lambda i: i in (0, 1, 10, 11, 12))
    assert st.status == "divergent"
    assert st.boundary_idx is not None


# -------------------------------------------------- ground truth (sim)


@pytest.fixture(scope="module")
def boundary_run(tmp_path_factory):
    """The 6-step loss ladder answered twice: exhaustively via
    `run_grid` (the oracle) and adaptively via `run_search` over the
    SAME ledger (probes join the exhaustive rows — zero new chunks)."""
    d = tmp_path_factory.mktemp("search")
    spec = _spec()
    led = str(d / "ledger.jsonl")
    grid_run = run_grid(spec.grid, Scheduler(ledger_path=led),
                        keep_states=())
    assert grid_run.report.clean
    search_run = run_search(spec, Scheduler(ledger_path=led))
    return spec, grid_run, search_run


def test_search_agrees_with_exhaustive_oracle(boundary_run):
    spec, grid_run, search_run = boundary_run
    splan = search_run.plan
    rows = {r["cell"]: r for r in grid_run.report.data["cells"]}
    truth = exhaustive_boundaries(splan, rows)
    rep = search_run.report.data
    assert rep["boundaries_found"] == len(splan.slices) == 1
    for row in rep["slices"]:
        assert row["status"] == "boundary"
        assert row["boundary_cell"] == truth[row["slice"]]
    # fewer cells probed than the lattice holds, even on 6 values
    assert rep["cells_probed"] < rep["cells_exhaustive"] == 6


def test_search_serves_probes_from_ledger_join(boundary_run):
    """Re-asking an answered question costs ZERO simulated chunks:
    every probe joins its exhaustive-run ledger row."""
    _, _, search_run = boundary_run
    rep = search_run.report.data
    assert rep["chunks_simulated"] == 0
    acct = rep["accounting"]
    assert acct["ledger_hits"] == rep["cells_probed"]
    assert acct["live_probes"] == 0


def test_report_roundtrip_and_schema_refusal(boundary_run):
    _, _, search_run = boundary_run
    rep = search_run.report
    again = SearchReport.from_json(
        json.dumps(rep.to_json(), sort_keys=True))
    assert again.to_json() == rep.to_json()
    assert again.search_digest == rep.search_digest
    assert again.clean
    with pytest.raises(ValueError, match="schema"):
        SearchReport.from_json(dict(rep.to_json(), schema=99))
    with pytest.raises(ValueError, match="search_digest"):
        SearchReport.from_json({"cells": []})
    assert "boundary" in rep.format()


def test_probe_sequence_rederives_identically(boundary_run):
    """Two searches of the same question walk the IDENTICAL probe
    sequence (cell ids in order) — the pure-function-of-digests pin,
    checked on real simulation verdicts via the ledger join."""
    spec, _, search_run = boundary_run
    seq_a = [p["cell"] for p in search_run.report.data["probes"]]
    # the automaton is deterministic given verdicts; verdicts are
    # deterministic given the spec — compare against a fresh compile
    splan2 = compile_search(SearchSpec.from_json(
        json.loads(spec.canonical_json())))
    assert splan2.search_digest == search_run.plan.search_digest
    assert [s.cell_ids for s in splan2.slices] \
        == [s.cell_ids for s in search_run.plan.slices]
    assert splan2.coarse_idx == search_run.plan.coarse_idx
    assert seq_a[:len(splan2.coarse_idx)] == [
        splan2.slices[0].cell_ids[i] for i in splan2.coarse_idx]


# ----------------------------------------------------- fleet memo seam


def test_fleet_workers_share_memo_table_in_process(tmp_path):
    """Satellite pin: two in-process `FleetWorker`s over one fleet
    dir + one shared memo table.  Worker "wa" is the only one stepped
    while prefix entries are pending, so IT runs the honest prefix and
    puts it in the table; worker "wb" is the only one stepped for the
    probe entries — every probe it completes must FORK from wa's
    table entry (memo_table_hits == probes, zero misses)."""
    import os

    from wittgenstein_tpu.matrix.search import _run_search_fleet
    from wittgenstein_tpu.serve.fleet import FleetWorker, fleet_paths
    from wittgenstein_tpu.serve.journal import SubmissionJournal

    spec = _spec()
    splan = compile_search(spec)
    fd = str(tmp_path / "fleet")
    table_dir = os.path.join(fd, "memo_table")
    paths = fleet_paths(fd)
    wa = FleetWorker(fd, "wa", lease_ttl_s=30.0,
                     memo_table=table_dir)
    wb = FleetWorker(fd, "wb", lease_ttl_s=30.0,
                     memo_table=table_dir)
    # the driver reads each probe's result from the ledger and, after
    # the last one, aggregates the workers' stats files at once:
    # publish them before each ledger row lands
    for w in (wa, wb):
        def append_ledger(*a, _w=w, _append=w.sched._append_ledger, **k):
            _w.write_stats()
            return _append(*a, **k)
        w.sched._append_ledger = append_ledger
    box = {}

    # poll_s: the driver gives a settled prefix two polls to land in
    # the table before it runs the probes unforked; wa puts it at the
    # end of its step, which under a loaded host outlasts 2 x 0.05 s
    def drive():
        box["run"] = _run_search_fleet(
            spec, splan, fleet_dir=fd, workers=2, spawn=False,
            poll_s=0.5, timeout_s=300.0)

    t = threading.Thread(target=drive, name="search-driver")
    t.start()
    journal = SubmissionJournal(paths["journal_dir"])
    try:
        while t.is_alive():
            pending = [e for e in journal.replay()]
            if any(e["rid"].startswith("sp") for e in pending):
                wa.step()
            else:
                wb.step()
            # in-process workers publish their stats snapshots here
            # (the subprocess main loop does it every poll cycle) so
            # the driver's aggregate_worker_stats sees the counters
            wa.write_stats()
            wb.write_stats()
            t.join(timeout=0.02)
    finally:
        t.join(timeout=300.0)
    assert not t.is_alive(), "fleet search driver hung"
    rep = box["run"].report
    assert rep.clean
    probed = rep.data["cells_probed"]
    assert probed < rep.data["cells_exhaustive"]
    # wa ran the prefix; wb's probes all hit wa's table entry
    assert wa.counters["memo_table_hits"] == 0
    assert wb.counters["memo_table_hits"] == probed
    assert wb.counters["memo_table_misses"] == 0
    assert wb.counters["prefix_chunks_saved"] == probed  # 1 chunk each
    assert wb.counters["search_probes_total"] == probed
    # the fleet resume block aggregates the worker counters
    acct = rep.data["accounting"]["resume"]
    assert acct["memo_table_hits"] == probed
    assert acct["memo_table_misses"] == 0


def test_search_counter_metrics_projection():
    """`refresh_search_counters` projects the four memo/search
    counters into the registry under their wtpu_* names (max-keeping:
    scrapes stay monotone)."""
    from wittgenstein_tpu.obs.metrics import MetricsRegistry
    from wittgenstein_tpu.serve.instrument import (
        SEARCH_COUNTERS, refresh_search_counters)
    m = MetricsRegistry()
    refresh_search_counters(m, {"memo_table_hits": 3,
                                "memo_table_misses": 1,
                                "prefix_chunks_saved": 9,
                                "search_probes_total": 4})
    text = m.exposition()
    for name in SEARCH_COUNTERS.values():
        assert name in text
    # max-keeping: a stale lower snapshot cannot regress the series
    refresh_search_counters(m, {"memo_table_hits": 2})
    assert "wtpu_memo_table_hits_total 3" in m.exposition()


# ------------------------------------------------------------ CLI + http


def test_cli_config_error_exit_2(capsys):
    cli = _cli()
    assert cli.main(["--spec", '{"bogus": 1}']) == 2
    assert "config error" in capsys.readouterr().err
    assert cli.main(["--spec", json.dumps(_spec().to_json()),
                     "--resume"]) == 2
    assert "--checkpoint-dir" in capsys.readouterr().err
    assert cli.main(["--spec", json.dumps(_spec().to_json()),
                     "--workers", "2"]) == 2
    assert "--fleet-dir" in capsys.readouterr().err


def test_cli_plan_only(capsys):
    cli = _cli()
    assert cli.main(["--spec", str(SPEC_PATH), "--plan-only"]) == 0
    out = capsys.readouterr().out
    assert "2 slice(s) x 32 'loss' values" in out
    assert "coarse ladder" in out


def test_checked_in_spec_digest_pin():
    """The checked-in boundary question is part of the acceptance
    surface: its digests may only move with a deliberate re-pin (the
    BENCH_NOTES r21 numbers are measured against exactly this)."""
    spec = SearchSpec.from_json(json.loads(SPEC_PATH.read_text()))
    assert spec.digest() == "71897572ddfeb0fd"
    assert spec.grid.grid_digest() == "414eeea427bbbe87"
    splan = compile_search(spec)
    assert len(splan.slices) == 2
    assert splan.summary()["chunks_exhaustive"] == 256


# ------------------------------------------------------- slow battery


VOLATILE = ("wall_s",)
RUN_LOCAL = ("wall_s", "accounting", "chunks_simulated",
             "probe_savings_ratio")


def _norm(rep, keys=VOLATILE):
    d = copy.deepcopy(rep.to_json() if hasattr(rep, "to_json")
                      else rep)
    for k in keys:
        d.pop(k, None)
    for row in d.get("cells", ()):
        row.pop("resumed_from_ms", None)
        row.pop("forked_from", None)
    return d


@pytest.fixture(scope="module")
def pinned_cold(tmp_path_factory):
    """One cold run of the checked-in boundary question (its ledger
    kept for the warm re-ask)."""
    d = tmp_path_factory.mktemp("pinned")
    spec = SearchSpec.from_json(json.loads(SPEC_PATH.read_text()))
    led = str(d / "ledger.jsonl")
    run = run_search(spec, Scheduler(ledger_path=led))
    return spec, led, run


@pytest.mark.slow
def test_pinned_question_savings_ratio_and_boundaries(pinned_cold):
    """The headline perf pin: the search finds the same boundary cells
    the exhaustive grid would, with >= 4x fewer simulated chunks."""
    spec, _led, run = pinned_cold
    rep = run.report.data
    assert rep["boundaries_found"] == 2
    by_slice = {r["slice"]: r for r in rep["slices"]}
    assert by_slice["seed=s0"]["boundary_label"] == "p060"
    assert by_slice["seed=s0"]["bracket"] == ["p050", "p060"]
    assert by_slice["seed=s1"]["boundary_label"] == "p020"
    assert rep["chunks_simulated"] * 4 <= rep["chunks_exhaustive"]
    assert rep["probe_savings_ratio"] >= 4.0
    assert rep["cells_probed"] < rep["cells_exhaustive"] == 64


@pytest.mark.slow
def test_pinned_question_cold_runs_bit_identical(pinned_cold,
                                                 tmp_path):
    """Determinism pin: two cold runs produce byte-identical
    SearchReport JSON modulo wall clock."""
    spec, _led, run = pinned_cold
    again = run_search(spec, Scheduler(
        ledger_path=str(tmp_path / "l2.jsonl")))
    a = json.dumps(_norm(run.report), sort_keys=True)
    b = json.dumps(_norm(again.report), sort_keys=True)
    assert a == b


@pytest.mark.slow
def test_pinned_question_warm_rerun_zero_chunks(pinned_cold):
    """Perf pin, second half: immediately re-asking the answered
    question completes with ZERO new simulated chunks."""
    spec, led, run = pinned_cold
    warm = run_search(spec, Scheduler(ledger_path=led))
    assert warm.report.data["chunks_simulated"] == 0
    acct = warm.report.data["accounting"]
    assert acct["live_probes"] == 0
    assert acct["ledger_hits"] == warm.report.data["cells_probed"]
    assert _norm(warm.report, RUN_LOCAL) == _norm(run.report,
                                                  RUN_LOCAL)


@pytest.mark.slow
def test_pinned_question_fleet_matches_single_process(pinned_cold,
                                                      tmp_path):
    """Determinism pin, fleet half: run_search(workers=2) — probes
    completed by two worker PROCESSES sharing the on-disk memo table —
    reproduces the single-process report bit-for-bit (normalized)."""
    spec, _led, run = pinned_cold
    fleet = run_search(spec, workers=2,
                       fleet_dir=str(tmp_path / "fleet"),
                       fleet_opts={"lease_ttl_s": 10.0,
                                   "timeout_s": 600.0,
                                   "poll_s": 0.1})
    assert _norm(fleet.report, RUN_LOCAL) == _norm(run.report,
                                                   RUN_LOCAL)
    acct = fleet.report.data["accounting"]["resume"]
    assert acct["fleet_workers"] == 2
    assert acct["memo_table_hits"] > 0


@pytest.mark.slow
def test_search_crash_kill_resume_bit_identical(tmp_path):
    """tools/crash_test.py --search in-process: SIGKILL a search
    campaign mid-flight, resume, and the final SearchReport is
    bit-identical (normalized) to the uninterrupted run's."""
    from tools.crash_test import run_search_crash_test
    res = run_search_crash_test(str(tmp_path), kills=1, seed=0)
    assert res["ok"], res
    assert res["boundaries_found"] == 1
