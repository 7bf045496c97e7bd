"""Lookups by name, the device checks, and the one result line."""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent          # benchmark/
REPO = ROOT.parent
#: JAX's persistent compile cache: a fixed path inside the checkout (the
#: path is part of the cache key, so a moving directory never hits)
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a chip missing from the peaks."""


# ------------------------------------------------------------- lookups

def load_benchmark(repo: Path = REPO) -> dict:
    return json.loads((repo / "BENCHMARK.json").read_text())


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{[e['name'] for e in entries]}")


def cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str, repo: Path = REPO) -> dict:
    """A configuration by name, from the file `BENCHMARK.json` gives."""
    entry = _named(bench["configs"], name, "configuration")
    return json.loads((repo / entry["file"]).read_text())


def traffic(name: str, root: Path = ROOT) -> dict:
    """A traffic mix by name: `<root>/traffic/<name>.json`."""
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def kind(name: str):
    """The general generator of a traffic kind (`kinds/<kind>.py`)."""
    return importlib.import_module(f"benchmark.kinds.{name}")


def reader(metric: str, root: Path = ROOT):
    """``read(record)`` of `metrics/<metric>.py` (names carry dots, so
    the file is loaded by path, not imported as a module name)."""
    path = root / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(bench: dict, cell_name: str, trace: bool) -> list:
    """The metrics a run of this cell reports: its end-to-end metrics
    with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if cell_name in m.get("workloads", [cell_name])]


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise NoChip(f"device kind {device_kind!r} is not in "
                     f"benchmark/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]


# ------------------------------------------------------------- clocks

def since_process_start() -> float:
    """Seconds from this process's start to now (Linux /proc), so that
    `setup_s` includes the interpreter's own start and imports."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])          # field 22, starttime
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


# ------------------------------------------------------------- device

def require_chips(chips: int) -> list:
    """The chips of this run, or NoChip: never a fall back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX found no accelerator (platform cpu)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    peaks(devs[0].device_kind)
    return devs[:chips]


def device_block(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def setup_jax():
    """JAX with the benchmark's compile cache and nothing of the
    program's environment knobs (the program runs its defaults)."""
    for key in [k for k in os.environ if k.startswith("WTPU_")]:
        del os.environ[key]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


# ------------------------------------------------------------- a run

def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t0: float, pre_start: float, require_device=True,
             repo: Path = REPO, patch=None, attack=None,
             info: dict | None = None) -> dict:
    """Run one cell once; returns the result object (not yet printed).

    `t0` is `time.monotonic()` at the entry's first line and
    `pre_start` the process's age then.  The CPU tests pass
    `require_device=False` (no look for a chip), another `repo` (a
    tiny cell added as files), `patch` (wraps the timed chunk program:
    a fault underneath) or `attack` (the control: a
    `ScenarioSpec.attack` perturbation planted in the program).
    `info`, if given, receives the set-up and check seconds.  The kind
    finds the protocol's reference under `repo`'s ``benchmark/`` (the
    ``root`` of its context)."""
    import jax
    bench = load_benchmark(repo)
    root = repo / "benchmark"
    c = cell(bench, name)
    mix = traffic(c["traffic"], root)
    devices = (require_chips(c["chips"]) if require_device
               else jax.devices()[:c["chips"]])
    ctx = {"cell": c, "config": config(bench, c["config"], repo),
           "traffic": mix, "seed": int(seed), "seconds": float(seconds),
           "trace": bool(trace), "devices": devices, "t0": t0,
           "pre_start": pre_start, "root": root,
           "out_dir": root / ".out" / c["name"],
           "patch": patch, "attack": attack}
    record = kind(mix["kind"]).run(ctx)
    metrics = {}
    for m in metrics_of(bench, name, trace):
        value = reader(m["name"], root)(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = record["checks"]
    result = {"correct": all(v["value"] <= v["limit"]
                             for v in checks.values()),
              "attempted": record["attempted"],
              "failed": record["failed"], "metrics": metrics,
              "device": record["device"]}
    if trace and record.get("breakdown"):
        result["breakdown"] = record["breakdown"]
    result["checks"] = checks
    if info is not None:
        info.update(setup_s=record["setup"]["setup_s"],
                    check_s=record.get("check_s", 0.0))
    return result


def main(argv=None, t0=None):
    t0 = time.monotonic() if t0 is None else t0
    pre_start = since_process_start() - (time.monotonic() - t0)
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    setup_jax()
    info = {}
    try:
        result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                          t0, pre_start, info=info)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    print(f"benchmark: set-up {info['setup_s']:.3f} s, check "
          f"{info['check_s']:.3f} s", file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
