"""CPU tests of the stage reduction (`benchmark/stages.py`).

A small recorded trace in the TPU's shape (an ops line of HLO texts, a
modules line, the compiled module's ``op_name`` paths): scopes under
``vmap(...)``, a fused op, an unscoped copy (the carry), a second
program's ops (named, not staged), and the stages' union against the
busy time `trace.reduce` gives, and the stage metrics' readers on it.  A
trace whose chunk program carries no scope reads None.  The traced tiny
stream cell, run through the harness on the CPU, reports the set-up's
trace and compile seconds.
"""

import json
import re
import shutil
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness, stages, trace  # noqa: E402

MS = 1_000_000
ROOT = "jit(chunk_batched)/while/body/closed_call"
HLO = f"""HloModule jit_chunk_batched, is_scheduled=true

%fused_computation.7 (param_0.1: s32[8]) -> s32[8] {{
  %param_0.1 = s32[8]{{0}} parameter(0)
  ROOT %gather.3 = s32[8]{{0}} gather(s32[8]{{0}} %param_0.1), metadata={{op_name="{ROOT}/protocol.step/vmap(handel.verify)/gather"}}
}}

%fused_computation.8 (param_0.2: s32[8]) -> s32[8] {{
  %param_0.2 = s32[8]{{0}} parameter(0)
  %reshape.4 = s32[8]{{0}} reshape(s32[8]{{0}} %param_0.2), metadata={{op_name="{ROOT}/engine.bin/reshape"}}
  ROOT %scatter.5 = s32[8]{{0}} scatter(s32[8]{{0}} %reshape.4)
}}

ENTRY %main.9 (p: s32[8]) -> s32[8] {{
  %p = s32[8]{{0}} parameter(0)
  %fusion.1 = s32[8]{{0}} fusion(s32[8]{{0}} %p), kind=kLoop, calls=%fused_computation.7
  %slice.2 = s32[8]{{0}} slice(s32[8]{{0}} %fusion.1), metadata={{op_name="{ROOT}/engine.inbox/slice"}}
  %sort.3 = s32[8]{{0}} sort(s32[8]{{0}} %slice.2), metadata={{op_name="{ROOT}/engine.bin/sort"}}
  %fusion.4 = s32[8]{{0}} fusion(s32[8]{{0}} %sort.3), kind=kLoop, calls=%fused_computation.7, metadata={{op_name="{ROOT}/protocol.step/vmap(handel.receive)/add"}}
  %add.5 = s32[8]{{0}} add(s32[8]{{0}} %fusion.4, s32[8]{{0}} %p), metadata={{op_name="{ROOT}/engine.route/add"}}
  %fusion.7 = s32[8]{{0}} fusion(s32[8]{{0}} %add.5), kind=kCustom, calls=%fused_computation.8
  ROOT %copy.6 = s32[8]{{0}} copy(s32[8]{{0}} %add.5)
}}
"""


def _ev(name, a, b):
    return (f"%{name} = s32[8]{{0}} op()", a * MS, b * MS)


OPS = [("while.9", 11 * MS, 70 * MS),           # a wrapper: not innermost
       _ev("slice.2", 11, 13), _ev("fusion.1", 14, 20),
       _ev("fusion.4", 20, 30), _ev("add.5", 30, 35), _ev("sort.3", 35, 50),
       _ev("copy.6", 50, 60),
       _ev("fusion.8", 72, 80)]                  # the check's reduction
MODULES = [("jit_chunk_batched(123)", 10 * MS, 70 * MS),
           ("jit_summarize(456)", 71 * MS, 81 * MS)]
HOST = [("bench.window", 0, 100 * MS), ("bench.sync", 60 * MS, 100 * MS)]


def test_op_names_follow_a_fusion_to_its_root():
    names = stages.op_names(HLO)
    assert names["fusion.1"].endswith("vmap(handel.verify)/gather")
    assert names["fusion.4"].endswith("vmap(handel.receive)/add")
    assert names["copy.6"] is None and names["p"] is None
    # a root without metadata: what the fused computation's ops carry
    assert stages.stage_of(names["fusion.7"]) == ("engine.bin", None)
    assert stages.stage_of(names["fusion.1"]) == ("protocol.step",
                                                  "handel.verify")
    assert stages.stage_of(names["copy.6"]) == (stages.CARRY, None)
    assert stages.scopes("a/vmap(jvp(b.c))/d") == ["a", "b.c", "d"]


def test_stage_reduction_on_a_recorded_trace():
    names = {"jit_chunk_batched": stages.op_names(HLO)}
    red = stages.reduce([(OPS, MODULES)], HOST, names, seed_sim_ms=40)
    s = red["stages"]
    assert s["engine.inbox"] == pytest.approx(0.002)
    assert s["protocol.step"] == pytest.approx(0.016)   # fusions 1 and 4
    assert s["engine.route"] == pytest.approx(0.005)
    assert s["engine.bin"] == pytest.approx(0.015)
    assert s["engine.carry"] == pytest.approx(0.010)    # the copy
    assert red["sub"] == pytest.approx(
        {"protocol.step/handel.verify": 0.006,
         "protocol.step/handel.receive": 0.010})
    assert red["modules"] == pytest.approx({"jit_summarize": 0.008})
    # the stages and the other module make up busy time, op for op
    busy = trace.reduce([OPS], HOST)["busy_s"]
    assert red["busy_s"] == pytest.approx(busy)
    assert sum(s.values()) + 0.008 == pytest.approx(busy)
    assert red["ms_per_simms"]["engine.bin"] == pytest.approx(0.375)
    assert red["unnamed_ops"] == 0
    assert red["carry_ops"] == [["copy.6 s32[8]{0}", None,
                                 pytest.approx(0.010)]]
    assert stages.reduce([(OPS, MODULES)], HOST)["unnamed_ops"] == 6
    rows = stages.device_stages(red)
    assert [r[0] for r in rows] == [
        "engine.inbox", "protocol.step", "protocol.step/handel.receive",
        "protocol.step/handel.verify", "engine.route", "engine.bin",
        "engine.carry", "jit_summarize"]


@pytest.mark.parametrize("op_name,want", [
    (f"{ROOT}/vmap(protocol.step)/pingpong.reply/jit(_where)/select_n",
     ("protocol.step", "pingpong.reply")),
    (f"{ROOT}/protocol.step/vmap(gsf.merge)/engine.bin/add",
     ("protocol.step", "gsf.merge")),
    (f"{ROOT}/protocol.step/gather", ("protocol.step", None)),
    (f"{ROOT}/engine.route/vmap(jit(clip))/max", ("engine.route", None)),
    # ops XLA merged (op_names of the chip's exact chunk program)
    (f"{ROOT}/engine.inbox/broadcast_in_dim;engine.inbox/reshape",
     ("engine.inbox", None)),
    (f"{ROOT}/protocol.step/vmap(handel.receive)/reshape;"
     "protocol.step/vmap(handel.disseminate)/reshape",
     ("protocol.step", "handel.receive")),
])
def test_a_sub_scope_is_any_dotted_scope_under_the_stage(op_name, want):
    assert stages.stage_of(op_name) == want


@pytest.mark.parametrize("protocol", ["handel", "pingpong"])
def test_sub_scopes_of_any_protocol_reach_the_table(protocol):
    text = HLO.replace("handel.", f"{protocol}.")
    red = stages.reduce([(OPS, MODULES)], HOST,
                        {"jit_chunk_batched": stages.op_names(text)})
    assert red["sub"] == pytest.approx(
        {f"protocol.step/{protocol}.verify": 0.006,
         f"protocol.step/{protocol}.receive": 0.010})
    assert [r[0] for r in stages.device_stages(red)][1:4] == [
        "protocol.step", f"protocol.step/{protocol}.receive",
        f"protocol.step/{protocol}.verify"]


def test_two_chips_average_and_clip_to_the_window():
    names = {"jit_chunk_batched": stages.op_names(HLO)}
    late = [(n, s + 40 * MS, e + 40 * MS) for n, s, e in OPS[1:3]]
    red = stages.reduce([(OPS, MODULES),
                         (late, [("jit_chunk_batched(1)", 50 * MS,
                                  60 * MS)])],
                        [("bench.window", 0, 55 * MS)], names)
    # chip 0: slice 2 ms; chip 1: slice 51-53 ms -> 2 ms
    assert red["stages"]["engine.inbox"] == pytest.approx(0.002)
    # chip 0: fusion.1 6 ms + fusion.4 10 ms; chip 1: fusion.1 54-55
    assert red["stages"]["protocol.step"] == pytest.approx(0.0085)


@pytest.mark.parametrize("names", [None, "unscoped"])
def test_a_chunk_program_without_scopes_reads_none(names):
    if names == "unscoped":
        text = re.sub(r"(engine\.\w+|protocol\.step|vmap\(handel\.\w+\))/",
                      "", HLO)
        assert "handel." not in text
        names = {"jit_chunk_batched": stages.op_names(text)}
    red = stages.reduce([(OPS, MODULES)], HOST, names, seed_sim_ms=40)
    assert red["stages"] is None and red["ms_per_simms"] is None
    assert red["sub"] == {}
    assert red["modules"] == pytest.approx({"jit_summarize": 0.008})
    assert stages.device_stages(red) == [["jit_summarize",
                                          pytest.approx(0.008)]]


def test_no_window_or_device_no_table():
    assert stages.reduce([], HOST) is None
    assert stages.reduce([(OPS, MODULES)], [("bench.init", 0, 5)]) is None
    assert stages.device_stages(None) == []


def _tiny_repo(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
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
    (bdir / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bdir / "traffic" / "tiny-stream.json").write_text(json.dumps(
        {"kind": "stream", "seeds": 3, "chunk_ms": 20, "span_ms": 40,
         "trace_chunks": 2, "check": {"seeds": 3, "chunks": 2}}))
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "benchmark/configs/tiny.json",
                         "reduced": ["node_count"], "why": "test"}]
    bench["workloads"] = [{"name": "tiny.cell", "config": "tiny",
                           "traffic": "tiny-stream", "chips": 1,
                           "why": "test"}]
    for m in bench["per_layer"]:
        m["workloads"] = ["tiny.cell"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


STAGE_METRICS = {f"{s}_ms_per_simms": s for s in stages.STAGES
                 + (stages.CARRY,)}


def test_stage_metrics_read_the_recorded_trace():
    names = {"jit_chunk_batched": stages.op_names(HLO)}
    red = stages.reduce([(OPS, MODULES)], HOST, names, seed_sim_ms=40)
    record = {"trace": {"stages": red["ms_per_simms"]}}
    got = {m: harness.reader(m)(record) for m in STAGE_METRICS}
    assert got == pytest.approx({m: 1000.0 * red["stages"][s] / 40
                                 for m, s in STAGE_METRICS.items()})
    # the five stages and the other module make up busy time
    assert sum(got.values()) + 1000.0 * 0.008 / 40 == pytest.approx(
        1000.0 * red["busy_s"] / 40)
    assert all(harness.reader(m)({"trace": {"stages": None}}) is None
               for m in STAGE_METRICS)


def test_traced_tiny_cell_reports_setup_trace_and_compile(tmp_path):
    res = harness.run_cell("tiny.cell", 2 ** 31 + 777, 0.5, True,
                           time.monotonic(), 0.0, require_device=False,
                           repo=_tiny_repo(tmp_path))
    assert res["correct"] is True
    metrics = res["metrics"]
    assert metrics["setup.trace_s"]["value"] > 0
    assert metrics["setup.compile_s"]["value"] > 0
    # the CPU has no accelerator plane: no stage table, nothing made up
    assert not set(STAGE_METRICS) & set(metrics)
    assert "breakdown" not in res
