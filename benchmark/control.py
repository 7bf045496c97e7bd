"""The control, at a cell's own size on the chip: the cell's program with
one guarantee broken, which the comparison must refuse.

    python3 benchmark/control.py --workload <name> --seconds <s> --seeds <n> [<n> ...]

The control plants `CONTROL` (a `ScenarioSpec.attack`, the request
plane's `obs.diff.FaultInjector`): at one simulated ms one node's
receive counter is off by one, in every seed of the batch.  Every
seed's run prints its result line; each must read `correct: false`.
The benchmark's own runs never run this (PERF.md gives the readings).
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import harness  # noqa: E402

CONTROL = {"at_ms": 7, "leaf": "nodes.msg_received", "node": 5, "delta": 1}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    harness.setup_jax()
    for seed in a.seeds:
        res = harness.run_cell(a.workload, seed, a.seconds, False,
                               time.monotonic(), 0.0, attack=CONTROL)
        print(json.dumps({"seed": seed, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
