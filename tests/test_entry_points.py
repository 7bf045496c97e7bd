"""Entry-point contracts: no path switches backend or hides a failure.

`chip_smoke.py` refuses a non-TPU backend; `bench.py` runs on the
backend JAX_PLATFORMS selects and names it; `tools/bench_suite.py`
exits non-zero when a stage fails; a fleet asks for no more worker
processes than the host's chips admit.
"""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if not k.startswith("WTPU_")}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR="", **extra)
    return env


def test_chip_smoke_refuses_a_non_tpu_backend():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=_env(), capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU, found 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_bench_names_the_selected_backend():
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env=_env(WTPU_BENCH_PROTO="pingpong", WTPU_BENCH_NODES="64",
                 WTPU_BENCH_SEEDS="2", WTPU_BENCH_MS="200",
                 WTPU_BENCH_REPS="1", WTPU_METRICS="0", WTPU_AUDIT="0",
                 WTPU_ROUTE_STATS="0", WTPU_LEDGER="0"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["platform"] == "cpu"
    assert line["device"]["count"] >= 1
    assert line["metric"] == "pingpong_64n_2seeds_agg_sim_ms_per_sec"
    src = (REPO / "bench.py").read_text()
    assert "_cpu_fallback" not in src and "execve" not in src


def _bench_suite():
    spec = importlib.util.spec_from_file_location(
        "bench_suite_under_test", REPO / "tools" / "bench_suite.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_suite_exit_code_reports_a_failed_stage(monkeypatch, capsys):
    suite = _bench_suite()

    def boom():
        raise RuntimeError("stage fell over")

    monkeypatch.setenv("WTPU_LEDGER", "0")
    monkeypatch.setattr(suite, "CONFIGS",
                        {"boom": boom, "fine": lambda: {"value": 1.0}})
    assert suite.main(["fine", "--no-history"]) == 0
    assert suite.main(["fine", "boom", "--no-history"]) == 1
    out = capsys.readouterr()
    assert "stage fell over" in out.out
    assert "1 stage(s) failed: boom" in out.err


def test_fleet_refuses_more_workers_than_the_chips_admit(monkeypatch,
                                                         tmp_path):
    from wittgenstein_tpu.serve import fleet
    monkeypatch.setattr(fleet, "_tpu_host", lambda: True)
    with pytest.raises(ValueError, match="admits 1.*Remedy"):
        fleet.check_fleet_size(2, env={})
    with pytest.raises(ValueError, match="Remedy"):
        fleet.spawn_workers(tmp_path, 2, env={})
    assert not (tmp_path / "journal").exists()   # refused before spawning
    fleet.check_fleet_size(1, env={})
    fleet.check_fleet_size(4, env={"JAX_PLATFORMS": "cpu"})
    monkeypatch.setattr(fleet, "_tpu_host", lambda: False)
    fleet.check_fleet_size(4, env={})


def test_chip_smoke_names_a_kernel_its_launcher_refuses(monkeypatch):
    """Phase c compiles the window with the route kernel forced on; a
    build-time refusal is named and the window compiles without it."""
    monkeypatch.syspath_prepend(str(REPO))
    import chip_smoke
    from wittgenstein_tpu.ops.pallas_route import route_enabled

    def fake_compile(step, init):
        if route_enabled():
            raise ValueError("pallas_route: no destination block obeys "
                             "the TPU (8, 128) tiling rule")
        return step, 0.0

    monkeypatch.setattr(chip_smoke, "_compile", fake_compile)
    (compiled, _), refused = chip_smoke._compile_kernel_window(
        ("with_route", None), ("without_route", None))
    assert compiled == "without_route"
    assert "(8, 128) tiling" in refused["bin_into_ring_planes"]


def test_bench_suite_fleet_smoke_passes_the_guard_on_a_tpu_host(
        monkeypatch):
    """The suite's fleet stage pins its workers to the CPU, so the
    one-worker-per-chip guard admits it on a TPU host."""
    from wittgenstein_tpu.serve import fleet
    monkeypatch.setattr(fleet, "_tpu_host", lambda: True)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(ValueError, match="admits 1"):
        fleet.check_fleet_size(2)
    out = _bench_suite().bench_fleet_smoke()
    assert out["value"] == 4
