"""Run one benchmark cell once and print the result line last.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exit 0 with the result as the last line of standard output; exit 3 and
no result where JAX finds no accelerator or fewer chips than the cell
asks for.
"""

import time

T0 = time.monotonic()   # before any heavy import: set-up starts here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
