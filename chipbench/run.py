"""Run one benchmark cell once (see chipbench/harness.py).

    python3 chipbench/run.py --workload <config>.<mix> --seed <n> \
        --seconds <s> --trace <0|1>

The last line on stdout is the result; exit code 3 with no result where
JAX finds no TPU, or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    from chipbench.harness import main

    sys.exit(main(sys.argv[1:], t_start=T_START, root=ROOT))
