"""One rehearsal run of a cell with a fault planted in the program.

  python fault_run.py <workload> <fault|none> <seed>

Skips the look for a chip (``--rehearse``: smoke sizes, CPU) and drives
the rest of a run; the last line of standard output is its result.
"""
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import cli, faults, spec  # noqa: E402

workload, fault, seed = sys.argv[1], sys.argv[2], sys.argv[3]
sys.path.insert(0, str(spec.ROOT / "src"))
if fault == "none":
    import contextlib

    plant = contextlib.nullcontext()
else:
    plant = {**faults.TRAIN, **faults.SERVE}[fault]()
with plant:
    sys.exit(cli.main(["--workload", workload, "--seed", seed,
                       "--seconds", "2", "--trace", "0", "--rehearse"],
                      t_start=T0))
