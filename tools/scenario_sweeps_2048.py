"""Reference-scale Handel scenario sweeps -> reports/SCENARIO_SWEEPS_2048.md.

Runs the three round-3 sweeps (logErrors / logExtraCycle /
logContactedNode, HandelScenarios.java:365,568-632) at the reference's
default scenario scale — 2048 nodes (HandelScenarios.java:61-123) — with
>= 8 seeds per point, and records the output as a committed report.
Platform-labeled: the numbers come from whatever backend JAX selects
(JAX_PLATFORMS).

Usage: python tools/scenario_sweeps_2048.py [out_dir]
"""

import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax  # noqa: E402

from wittgenstein_tpu.scenarios import handel_scenarios  # noqa: E402


def main():
    out_dir = pathlib.Path(sys.argv[1]) if len(sys.argv) > 1 else \
        REPO / "reports"
    out_dir.mkdir(exist_ok=True)
    n, seeds = 2048, 8
    t0 = time.time()

    def dicts(csv):
        return [dict(zip(csv.columns, row)) for row in csv.rows]

    rows = {}
    csv = handel_scenarios.log_errors(error_rate=0.2, counts=(n,),
                                      seeds=seeds, out_dir=out_dir)
    rows["errors"] = dicts(csv)
    csv = handel_scenarios.extra_cycle_sweep(
        cycles=(10, 20, 40), nodes=n, seeds=seeds, out_dir=out_dir)
    rows["extra_cycle"] = dicts(csv)
    csv = handel_scenarios.contacted_node_sweep(
        fast_paths=(0, 10, 40), nodes=n, seeds=seeds, out_dir=out_dir)
    rows["fast_path"] = dicts(csv)

    wall = time.time() - t0
    platform = jax.default_backend()

    def table(key, xcol):
        lines = [f"| {xcol} | avg doneAt (ms) | msgs sent/node | done frac |",
                 "|---|---|---|---|"]
        for r in rows[key]:
            lines.append(f"| {r[xcol]} | {r['avg_done_ms']} "
                         f"| {r['msg_sent_avg']} | {r['frac_done']} |")
        return "\n".join(lines)

    report = out_dir / "SCENARIO_SWEEPS_2048.md"
    report.write_text(f"""# Reference-scale Handel sweeps (2048 nodes x {seeds} seeds)

The reference's default scenario config (HandelScenarios.java:61-123 —
2048 nodes, 10% dead unless the sweep varies it, threshold 0.99*live,
pairing 4 ms, levelWait 50 ms, period 20 ms, fastPath 10, CITIES
builder), platform **{platform}**, wall-clock {wall / 60:.1f} min total.

## Fail-silent errors at 20% (logErrors, HandelScenarios.java:365-430)

{table("errors", "nodes")}

## extraCycle sweep (logExtraCycle, :568-585)

{table("extra_cycle", "extra_cycle")}

## Fast-path peer count (logContactedNode, :588-632)

{table("fast_path", "fast_path")}

Full point CSVs: handel_errors.csv, handel_extra_cycle.csv,
handel_fastpath.csv (+ PNG plots) in this directory.
""")
    print(f"wrote {report} ({wall / 60:.1f} min, platform {platform})",
          flush=True)


if __name__ == "__main__":
    main()
