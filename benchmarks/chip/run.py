#!/usr/bin/env python3
"""One run of one cell of the on-chip benchmark (see chipbench/cli.py).

  python benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
