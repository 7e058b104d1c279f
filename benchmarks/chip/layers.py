#!/usr/bin/env python3
"""The layer breakdown of one traced run of a cell, from the program's
own spans and scopes (``chipbench/program.py``).

  python benchmarks/chip/layers.py --workload <cell> --seed <n> \\
      --seconds <s> [--slice <path> --slice-span <name> \\
      --slice-index <i> --slice-ms <before> <after>]

Runs the cell as ``run.py --trace 1`` does (it needs a TPU), keeps the
trace until it is read, and prints one JSON line: the cell's end-to-end
and per-layer metrics as ``run.py`` reads them, the layer metrics of the
program's instrumentation, self time by scope, the ten longest idle gaps
named by the program's spans, and the unscoped ops.  ``--slice`` writes
device 0's ops and the spans from ``before`` ms ahead of the end of the
``i``-th ``--slice-span`` to ``after`` ms past it, as a recorded trace
for the tests.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import program as pg  # noqa: E402
from chipbench import spec  # noqa: E402
from chipbench import trace as tr  # noqa: E402


def layer_metrics(t: pg.ProgramTrace) -> dict:
    """The program's layer metrics, in ms per train step or per decode
    call (None where the trace has nothing to read)."""
    step, call = "train.step", "serve.decode"
    out = {
        "fwd_ms": pg.layer_ms(t, step, backward=False),
        "bwd_ms": pg.layer_ms(t, step, backward=True),
        "optimizer_ms": pg.layer_ms(t, step, ("optimizer",)),
        "ssd_ms": pg.layer_ms(t, step, ("ssd",)),
        "lambda_decode_ms": pg.layer_ms(t, step, ("lambda_decode",)),
        "train_host_ms": pg.span_ms(t, pg.TRAIN_HOST, step),
        "weight_cast_ms": pg.layer_ms(t, call, ("weight_cast",)),
        "decode_dispatch_ms": pg.span_ms(t, (call,), call),
    }
    return {k: v for k, v in out.items() if v is not None}


def breakdown(t: pg.ProgramTrace) -> dict:
    """Busy and self time by scope, in ms per train step (or per decode
    call in a serve cell), the idle gaps and the unscoped ops."""
    per = "train.step" if t.in_window("train.step") else "serve.decode"
    n = len(t.in_window(per))
    secs = pg.scope_seconds(t)
    busy = sum(tr.length((o.start, o.end) for o in t.ops(d))
               for d in t.devices) / len(t.devices)
    return {"per": per, "count": n,
            "busy_ms": 1e3 * busy / n if n else None,
            "self_ms": {k: 1e3 * v / n for k, v in sorted(secs.items())}
            if n else {},
            "idle_gaps": pg.idle_gaps(t),
            "top_unscoped": pg.top_unscoped(t)}


def cut(t: pg.ProgramTrace, span: str, index: int, before_ms: float,
        after_ms: float) -> pg.ProgramTrace:
    end = sorted(t.in_window(span))[index][1]
    lo, hi = end - 1e-3 * before_ms, end + 1e-3 * after_ms
    dev = min(t.devices)
    return pg.ProgramTrace(
        {dev: [o for o in t.devices[dev] if lo <= o.start and o.end <= hi]},
        {k: [s for s in v if s[1] > lo and s[0] < hi]
         for k, v in t.spans.items()},
        (lo, hi))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--slice", default="")
    ap.add_argument("--slice-span", default="train.step")
    ap.add_argument("--slice-index", type=int, default=1)
    ap.add_argument("--slice-ms", type=float, nargs=2, default=(100, 10))
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" or len(jax.devices()) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} TPU chips")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from chipbench.cli import Run
    from chipbench.record import Record

    record = Record(traced=True)
    runner = importlib.import_module("chipbench." + cell.traffic["kind"])
    trace_dir = tempfile.mkdtemp(prefix="chipbench-layers-")
    try:
        out = runner.run(cell, args.seed, args.seconds, record, trace_dir)
        record.values["setup_s"] = record.values["window_start"] - T_START
        t0 = time.perf_counter()
        path = tr.newest_xplane(trace_dir)
        prog = pg.read(path, range(cell.chips))
        trace = prog.harness_trace()
        read_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(cell, record, trace, spec.peaks(dev.device_kind))
    metrics = {}
    for m in cell.end_to_end + cell.per_layer:
        value = cell.metric_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = value
    checks = {k: {"value": v, "limit": cell.limits[k]["limit"]}
              for k, v in out["checks"].items()}
    result = {
        "workload": cell.name, "seed": args.seed,
        "correct": all(c["value"] <= c["limit"] for c in checks.values())
        and out["failed"] == 0,
        "metrics": metrics, "layers": layer_metrics(prog),
        "breakdown": breakdown(prog),
        "harness_breakdown": {"device_ops": tr.top_ops(trace),
                              "idle_gaps": tr.idle_gaps(trace)},
        "busy_s": tr.mean_busy_s(trace), "window_s": trace.window_s,
        "read_s": read_s, "checks": checks}
    if args.slice:
        part = cut(prog, args.slice_span, args.slice_index,
                   *args.slice_ms)
        pg.save(part, args.slice)
        result["slice"] = {"path": args.slice,
                           "ops": sum(map(len, part.devices.values()))}
    print(json.dumps(result, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
