"""``run.py``: one run of one benchmark cell.

  python benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs a TPU with at least the
cell's chips: without one it exits non-zero and prints no result.  The
last line of standard output is the result as one JSON object; the
numbers that decide ``correct`` are the last lines of standard error.
``--rehearse`` runs the cell at the configuration's and the traffic's
``smoke`` sizes on whatever JAX finds, and prints counts and checks
only, never a device metric.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import math
import shutil
import sys
import tempfile
from typing import Dict, Optional

from chipbench import spec


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    cell: spec.Cell
    record: object
    trace: Optional[object]
    peaks: Dict


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke sizes, any platform, no device metrics")
    return ap.parse_args(argv)


def fail(msg: str) -> None:
    print(f"run.py: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def main(argv=None, t_start: float = 0.0) -> int:
    args = parse(argv)
    if not (spec.ROOT / "src" / "repro" / "api" / "session.py").is_file():
        fail(f"the program is not in this checkout ({spec.ROOT}/src)")
    cell = spec.load_cell(args.workload, smoke=args.rehearse)
    sys.path.insert(0, str(spec.ROOT / "src"))

    import jax

    devices = jax.devices()
    peaks = None
    if not args.rehearse:
        if devices[0].platform != "tpu":
            fail(f"JAX finds no TPU (platform {devices[0].platform!r}); "
                 f"nothing is measured on another device")
        if len(devices) < cell.chips:
            fail(f"{cell.name} needs {cell.chips} chips, JAX finds "
                 f"{len(devices)}")
        peaks = spec.peaks(devices[0].device_kind)
        if peaks is None:
            fail(f"no peaks for device kind {devices[0].device_kind!r} "
                 f"in peaks.json")
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    elif len(devices) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} devices, JAX finds "
             f"{len(devices)}")

    from chipbench.record import Record

    record = Record(traced=bool(args.trace))
    runner = importlib.import_module("chipbench." + cell.traffic["kind"])
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace and not args.rehearse else None
    try:
        out = runner.run(cell, args.seed, args.seconds, record, trace_dir)
        record.values["setup_s"] = record.values["window_start"] - t_start
        trace = None
        if trace_dir:
            from chipbench import trace as tr

            trace = tr.read_xplane(tr.newest_xplane(trace_dir),
                                   range(cell.chips))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    checks = {}
    for name, value in out["checks"].items():
        limit = cell.limits[name]["limit"]
        checks[name] = {"value": value, "limit": limit}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    correct = correct and out["failed"] == 0 and out["attempted"] > 0

    if args.rehearse:
        result = {"rehearsal": True, "platform": devices[0].platform,
                  "correct": correct, "attempted": out["attempted"],
                  "failed": out["failed"],
                  "compared": record.values.get("compared"),
                  "checks": checks}
    else:
        run = Run(cell, record, trace, peaks)
        wanted = cell.per_layer if args.trace else cell.end_to_end
        metrics = {}
        for m in wanted:
            value = cell.metric_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev = devices[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": cell.chips,
                  "memory_peak_bytes": record.values["memory_peak_bytes"]}
        result = {"correct": correct, "attempted": out["attempted"],
                  "failed": out["failed"], "metrics": metrics,
                  "device": device}
        if trace is not None:
            from chipbench import trace as tr

            device["busy_s"] = tr.mean_busy_s(trace)
            device["window_s"] = trace.window_s
            result["breakdown"] = {"device_ops": tr.top_ops(trace),
                                   "idle_gaps": tr.idle_gaps(trace)}
        result["compared"] = record.values.get("compared")
        result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
