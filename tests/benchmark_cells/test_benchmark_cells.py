"""CPU tests of the benchmark (`benchmark/`, `BENCHMARK.json`).

A tiny cell is added as files only (a config, a traffic mix, a copy of
the reference modules and a BENCHMARK.json entry in a scratch checkout)
and driven through the whole harness with the look for a chip skipped:
the result line of both traffic kinds, the control and each fault the
cells can have (which must all come out not correct), the reference
taken from the cell's checkout (and refused there when missing or
claimed twice), the trace reduction on a small recorded trace with
nested events, and the contract's static rules.
"""

import ast
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import check, harness, trace  # noqa: E402
from benchmark.control import CONTROL  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# --------------------------------------------------------- lookups

@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_lookup_by_name(cell):
    c = harness.cell(BENCH, cell)
    cfg = harness.config(BENCH, c["config"])
    mix = harness.traffic(c["traffic"])
    import wittgenstein_tpu.models  # noqa: F401  (registers protocols)
    from wittgenstein_tpu.core.protocol import get_protocol
    protocol = cfg["spec"]["protocol"]
    assert get_protocol(protocol)
    assert check.reference(harness.ROOT, protocol).PROTOCOL == protocol
    assert harness.kind(mix["kind"]).run
    names = {m["name"] for m in harness.metrics_of(BENCH, cell, False)}
    assert {"sim_ms_per_s", "setup_s", "peak_hbm_gb"} <= names
    layers = harness.metrics_of(BENCH, cell, True)
    assert layers and all(m["moves"] in names for m in layers)


#: every metric, and the served kind's readers, which no cell reads yet
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]] \
    + ["serve.settle_share", "serve.launch_share"]
#: a record as a run leaves it, without a trace or the served spans
RECORD = {"window": {"seconds": 2.0, "seed_sim_ms": 80, "lockstep_ms": 40},
          "setup": {"setup_s": 1.0, "load_s": 0.4,
                    "compile": {"trace_s": 3.0, "compile_s": 2.0}},
          "device": {"memory_peak_bytes": 10 ** 9}}


@pytest.mark.parametrize("metric", METRICS)
def test_every_metric_has_a_reader(metric):
    read = harness.reader(metric)
    if metric.startswith(("device.", "engine.", "serve.", "protocol.")):
        assert read(RECORD) is None        # nothing to read: left out
    else:
        assert read(RECORD) > 0


@pytest.mark.parametrize("metric", METRICS)
def test_every_metric_reads_a_traced_record(metric):
    """With a reduced trace (`kinds/stream.py`) and the served spans in
    the record, every metric reads a number."""
    record = json.loads(json.dumps(RECORD))
    record["trace"] = {"busy_s": 1.5, "ops": 400.0,
                       "stages": {"engine.inbox": 0.3, "protocol.step": 6.3,
                                  "engine.route": 1.0, "engine.bin": 2.7,
                                  "engine.carry": 3.3}}
    record["serve"] = {"window_s": 2.0, "launch_s": 1.5, "settle_s": 0.1}
    assert harness.reader(metric)(record) > 0


def test_peaks_table_refuses_unknown_devices():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.NoChip):
        harness.peaks("cpu")


def test_reference_imports_nothing_of_the_program():
    for path in (REPO / "benchmark" / "reference").rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                mods = ([a.name for a in node.names]
                        if isinstance(node, ast.Import)
                        else [node.module or ""])
                assert not any(m.startswith("wittgenstein_tpu")
                               for m in mods), path


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_reference_follows_the_program_ms_by_ms(config):
    """The protocol's numpy reference against the program's own per-ms
    engine, both cut by the reference's `TINY` and on the CPU: every
    compared value at every ms."""
    import numpy as np

    from benchmark.kinds.stream import reference_params
    from wittgenstein_tpu.core.network import step_ms
    from wittgenstein_tpu.serve.spec import ScenarioSpec
    body = dict(harness.config(BENCH, config)["spec"])
    ref = check.reference(harness.ROOT, body["protocol"])
    body["params"] = {**body["params"], **ref.TINY}
    spec = ScenarioSpec.from_json(body)
    seed, ms = 2 ** 31 - 7, 120
    proto = spec.build_protocol()
    net, ps = jax.jit(proto.init)(np.int32(seed))
    step = jax.jit(lambda n, p: step_ms(proto, n, p))
    want = ref.trajectory(reference_params(spec), seed, range(1, ms + 1),
                          check.device_tables(ref))
    for t in range(ms):
        net, ps = step(net, ps)
        got = {f: getattr(net.nodes, f) for f in ref.NODE_FIELDS}
        got.update({f: getattr(net, f) for f in check.ENGINE_LOSSES},
                   time=net.time)
        got.update({f: getattr(ps, f)
                    for f in ref.PROTO_FIELDS + ref.PROTO_LOSSES})
        for f, v in got.items():
            assert np.array_equal(np.asarray(v), want[t][f]), (t, f)


def test_reference_is_no_copy_of_the_program():
    prog = {p.read_bytes() for p in (REPO / "wittgenstein_tpu").rglob("*")
            if p.is_file()}
    for path in (REPO / "benchmark" / "reference").rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            assert path.read_bytes() not in prog, path


# --------------------------------------------------------- contract

def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert (REPO / p).is_dir() and ".." not in p
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    n_cells = 24                  # what later PRs may grow to
    budget = (2 + 14 * n_cells) * (BENCH["run_seconds"] + 60) \
        + n_cells * 2 * 90 + 1200
    assert 1 <= BENCH["run_seconds"] <= 51 and budget <= 43200
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert c["file"].startswith("benchmark/")
        data = json.loads((REPO / c["file"]).read_text())
        assert data["reduced"] == c["reduced"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(1, len(pairs) // 2)
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m["workloads"]) <= {w["name"]
                                       for w in BENCH["workloads"]}


# --------------------------------------------------------- trace

def test_trace_reduction_counts_innermost_ops_only():
    ms = 1_000_000
    # one device line: a module wrapper holds a while holding 3 ops
    ops = [("jit_run(123)", 10 * ms, 90 * ms), ("while.7", 12 * ms, 80 * ms),
           ("fusion.1", 12 * ms, 20 * ms), ("fusion.2", 25 * ms, 40 * ms),
           ("%sort.3 = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %a.1)",
            40 * ms, 70 * ms), ("copy.9", 95 * ms, 99 * ms)]
    host = [("bench.window", 0, 100 * ms), ("bench.init", 0, 9 * ms),
            ("bench.sync", 70 * ms, 100 * ms), ("bench.run", 0, 100 * ms)]
    red = trace.reduce([ops], host)
    assert red["window_s"] == pytest.approx(0.1)
    # fusions 8 + 15 ms, sort 30 ms and copy 4 ms; no wrapper counts
    assert red["busy_s"] == pytest.approx(0.057)
    assert red["busy_s"] <= red["window_s"]
    assert red["ops"] == 4
    names = [n for n, _ in red["device_ops"]]
    assert names[0] == "sort.3 (s32[8]{0}," and "while.7" not in names \
        and not any(n.startswith("jit_run") for n in names)
    gaps = {round(t, 6): n for n, t in red["idle_gaps"]}
    assert gaps[0.012] == "bench.init"       # 0-12 ms: inside the init
    assert gaps[0.025] == "bench.sync"       # 70-95 ms: the host waits
    assert sum(t for _, t in red["idle_gaps"]) == pytest.approx(0.043)


def test_trace_reduction_needs_a_device_and_a_window():
    assert trace.reduce([], [("bench.window", 0, 5)]) is None
    assert trace.reduce([[("f", 0, 5)]], [("bench.init", 0, 5)]) is None


# --------------------------------------------------------- tiny cells

def _tiny_repo(tmp_path, mix_name, mix, per_layer=()):
    bdir = tmp_path / "benchmark"
    (bdir / "configs").mkdir(parents=True)
    (bdir / "traffic").mkdir()
    shutil.copytree(REPO / "benchmark" / "metrics", bdir / "metrics")
    shutil.copytree(REPO / "benchmark" / "reference", bdir / "reference",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((REPO / "benchmark/configs/handel-2048-exact.json")
                     .read_text())
    cfg["spec"]["params"].update(node_count=64, nodes_down=6,
                                 threshold=57)
    cfg["reduced"] = ["node_count"]
    (bdir / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bdir / "traffic" / f"{mix_name}.json").write_text(json.dumps(mix))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "benchmark/configs/tiny.json",
                         "reduced": ["node_count"], "why": "test"}]
    bench["workloads"] = [{"name": "tiny.cell", "config": "tiny",
                           "traffic": mix_name, "chips": 1, "why": "test"}]
    bench["per_layer"] += list(per_layer)
    for m in bench["per_layer"]:
        m["workloads"] = ["tiny.cell"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


STREAM = {"kind": "stream", "seeds": 3, "chunk_ms": 20, "span_ms": 40,
          "trace_chunks": 2, "check": {"seeds": 3, "chunks": 2}}
SERVED = {"kind": "served", "clients": 2, "seeds_per_request": 2,
          "sim_ms": 40, "chunk_ms": 20, "obs": ["metrics", "audit"],
          "trace_requests": 1, "check": {"requests": 2}}
SERVE_LAYERS = [{"name": n, "unit": "%", "better": "lower",
                 "source": "program_span", "layer": "request plane",
                 "moves": "sim_ms_per_s"}
                for n in ("serve.settle_share", "serve.launch_share")]


def _run(repo, trace_on=False, seconds=1.0, **kw):
    return harness.run_cell("tiny.cell", 2 ** 31 + 12345, seconds,
                            trace_on, time.monotonic(), 0.0,
                            require_device=False, repo=repo, **kw)


def _assert_result_line(res, trace_on):
    line = json.loads(json.dumps(res))
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    for v in line["checks"].values():
        assert v["value"] <= v["limit"]
    names = set(line["metrics"])
    if trace_on:
        assert "setup.load_s" in names and "sim_ms_per_s" not in names
    else:
        assert {"sim_ms_per_s", "setup_s", "peak_hbm_gb"} == names
        assert line["metrics"]["sim_ms_per_s"]["value"] > 0


@pytest.mark.parametrize("trace_on", [False, True])
def test_tiny_stream_cell_added_as_files(tmp_path, trace_on):
    repo = _tiny_repo(tmp_path, "tiny-stream", STREAM)
    _assert_result_line(_run(repo, trace_on), trace_on)


def test_tiny_served_cell_added_as_files(tmp_path, monkeypatch):
    """Both runs compile nothing in the window, whichever groups the
    clients form: set-up ran each group width once."""
    from benchmark.kinds import served
    records, run = [], served.run
    monkeypatch.setattr(served, "run",
                        lambda ctx: records.append(run(ctx)) or records[-1])
    repo = _tiny_repo(tmp_path, "tiny-served", SERVED, SERVE_LAYERS)
    _assert_result_line(_run(repo), False)
    res = _run(repo, trace_on=True)
    assert {"serve.settle_share", "serve.launch_share"} \
        <= set(res["metrics"])
    assert res["correct"] is True
    assert res["attempted"] == SERVED["clients"] * SERVED["trace_requests"]
    assert [r["serve"]["compile_s"] for r in records] == [0.0, 0.0]


# A fault wraps a chunk program; what follows the state in its output
# (the served planes' carries) passes through.

def _unchanged(chunk):
    return lambda nets, ps: (nets, ps) + tuple(chunk(nets, ps)[2:])


def _half_batch(chunk):
    def run(nets, ps):
        out = chunk(nets, ps)
        h = nets.time.shape[0] // 2
        return jax.tree.map(lambda a, b: a.at[h:].set(b[h:]),
                            tuple(out[:2]), (nets, ps)) + tuple(out[2:])
    return run


def _altered(chunk):
    def run(nets, ps):
        nets, ps, *rest = chunk(nets, ps)
        sent = nets.nodes.msg_sent.at[:, 0].add(1)
        return (nets.replace(nodes=nets.nodes.replace(msg_sent=sent)), ps,
                *rest)
    return run


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered}


@pytest.mark.parametrize("fault", ["control", *FAULTS])
def test_control_and_faults_are_not_correct(tmp_path, fault):
    """Each fault the stream cells can have, planted under the timed
    path, and the control (one receive counter bumped at one ms, the
    `ScenarioSpec.attack` path), must come out `correct: false`.  (The
    cells run on one chip, so there is no exchange to leave out.)"""
    repo = _tiny_repo(tmp_path, "tiny-stream", STREAM)
    kw = ({"attack": CONTROL} if fault == "control" else
          {"patch": FAULTS[fault]})
    res = _run(repo, seconds=0.5, **kw)
    assert res["correct"] is False and res["failed"] > 0


def test_served_control_is_not_correct(tmp_path):
    repo = _tiny_repo(tmp_path, "tiny-served", SERVED)
    assert _run(repo, seconds=0.5, attack=CONTROL)["correct"] is False


@pytest.mark.parametrize("fault", list(FAULTS))
def test_served_faults_are_not_correct(tmp_path, fault):
    """The same faults under every program the request plane launches
    (the served cell's `check.requests` sample is one request)."""
    mix = {**SERVED, "check": {"requests": 1}}
    repo = _tiny_repo(tmp_path, "tiny-served", mix)
    res = _run(repo, seconds=0.5, patch=FAULTS[fault])
    assert res["correct"] is False and res["failed"] > 0


# --------------------------------------------------------- references

OFF_BY_ONE = """

_trajectory = trajectory


def trajectory(*args):
    out = _trajectory(*args)
    out[-1]["msg_received"][0] += 1
    return out
"""


@pytest.mark.parametrize("reference", ["copy", "off_by_one"])
def test_reference_is_taken_from_the_cells_checkout(tmp_path, reference):
    """The check runs the reference modules under the cell's checkout:
    a copy of them passes the cell, and the same copy with one value of
    its trajectory off by one fails it."""
    repo = _tiny_repo(tmp_path, "tiny-stream", STREAM)
    if reference == "off_by_one":
        sim = repo / "benchmark" / "reference" / "sim.py"
        sim.write_text(sim.read_text() + OFF_BY_ONE)
    res = _run(repo, seconds=0.5)
    assert res["correct"] is (reference == "copy")
    assert res["checks"]["mismatched_values"]["value"] == (
        0 if reference == "copy" else STREAM["check"]["seeds"])


def test_a_protocol_without_a_reference_is_refused_before_the_window(
        tmp_path):
    repo = _tiny_repo(tmp_path, "tiny-stream", STREAM)
    ref_dir = repo / "benchmark" / "reference"
    (ref_dir / "sim.py").unlink()
    built = []
    with pytest.raises(check.NoReference,
                       match=re.escape(str(ref_dir.resolve()))):
        _run(repo, patch=lambda chunk: built.append(chunk) or chunk)
    assert not built                 # refused before any program


def test_two_references_claiming_one_protocol_are_refused(tmp_path):
    repo = _tiny_repo(tmp_path, "tiny-stream", STREAM)
    ref_dir = repo / "benchmark" / "reference"
    shutil.copy(ref_dir / "sim.py", ref_dir / "sim_copy.py")
    with pytest.raises(check.NoReference, match=r"'sim\.py', 'sim_copy\.py'"):
        _run(repo)


def test_no_chip_no_result():
    """On the CPU the entry exits non-zero and prints no result line."""
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "correct" not in out.stdout
    assert "no accelerator" in out.stderr
