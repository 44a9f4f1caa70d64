"""Run one benchmark cell once on the chip this process finds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Prints the result as one JSON object on the last line of standard output,
and each number compared beside its limit as the last lines of standard
error. Exits nonzero, printing no result, when JAX finds no TPU.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START, root=ROOT))
