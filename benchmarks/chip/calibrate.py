#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from.

  python benchmarks/chip/calibrate.py --workload <cell> --seeds 11,12,... \\
      [--controls 3] [--faults 3] [--seconds 6] [--out readings.json]

One process, on the chip the cell asks for (``--rehearse``: smoke sizes,
any platform).  For every seed, the program's numbers as a run computes
them, over a window of ``--seconds`` (0 for training: the numbers come
from set-up's first steps).  For the first ``--controls`` seeds, the
control: the reference computed with float8 operands, put in the
program's place (training: float8 operands with the residual stream
in bfloat16, as the program keeps it; beside it, as a reading only,
the reference at the program's own bfloat16 precision).  For the first ``--faults`` seeds, each fault of
``chipbench/faults.py`` that the cell can have, planted in the program.
``run.py`` never runs any of this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import common, faults, spec  # noqa: E402
from chipbench.record import Record  # noqa: E402


TRAIN_CONTROL = "fp8+bf16res"
TRAIN_READINGS = ("bf16+bf16res",)


def control_train(cell, seed, rec):
    from chipbench import train

    loss_ref, grad_ref, change_ref = rec.values["reference"]
    n = int(cell.traffic["reference_steps"])
    keep = common.moving_leaves(grad_ref)

    def gaps(policy):
        loss_c, grad_c, change_c = train.reference(
            cell, seed, rec.values["feed"], n, policy=policy)
        return {"loss_gap": common.rel_gap(loss_c, loss_ref),
                "grad_gap": common.worst_leaf_gap(grad_c, grad_ref)["value"],
                "change_gap": common.worst_leaf_gap(change_c, change_ref,
                                                    keep)["value"],
                "loss": loss_c}

    out = gaps(TRAIN_CONTROL)
    out["readings"] = {p: gaps(p) for p in TRAIN_READINGS}
    return out


def control_serve(cell, seed, rec):
    from chipbench import serve

    rows = serve.reference_gaps(cell, seed, rec.values["sample"],
                                controls=("fp8",))
    return {"token_gap": max(r["fp8"] for r in rows)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--fault-names", default="")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, smoke=args.rehearse)
    sys.path.insert(0, str(spec.ROOT / "src"))
    import importlib

    import jax

    if not args.rehearse:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("calibrate.py: JAX finds no TPU")
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = cell.traffic["kind"]
    runner = importlib.import_module("chipbench." + kind)
    seconds = args.seconds if args.seconds is not None else (
        0.0 if kind == "train" else 6.0)
    plants = dict(faults.TRAIN if kind == "train" else faults.SERVE)
    if args.fault_names:
        plants = {k: v for k, v in plants.items()
                  if k in args.fault_names.split(",")}
    seeds = [int(s) for s in args.seeds.split(",")]
    out = {"workload": cell.name, "program": [], "control": [],
           "faults": {k: [] for k in plants}}

    def save():
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text(json.dumps(out, indent=1))

    def log(kind_, seed, checks, t0):
        save()
        print(f"[{kind_}] seed {seed}: "
              + ", ".join(f"{k} {v!r}" for k, v in checks.items())
              + f" ({time.perf_counter() - t0:.1f} s)", flush=True)

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        rec = Record(False)
        res = runner.run(cell, seed, seconds, rec)
        out["program"].append({"seed": seed, **res["checks"],
                               "compared": _plain(rec.values.get("compared"))})
        log("program", seed, res["checks"], t0)
        if i < args.controls:
            t0 = time.perf_counter()
            c = (control_train if kind == "train" else control_serve)(
                cell, seed, rec)
            out["control"].append({"seed": seed, **c})
            log("control", seed, c, t0)
        del rec
    for name, plant in plants.items():
        for seed in seeds[:args.faults]:
            t0 = time.perf_counter()
            with plant():
                res = runner.run(cell, seed, seconds, Record(False))
            out["faults"][name].append({"seed": seed, **res["checks"]})
            log(name, seed, res["checks"], t0)
    summary = {}
    for key in out["program"][0]:
        if key in ("seed", "compared"):
            continue
        summary[key] = {
            "lower": max(r[key] for r in out["program"]),
            "control": min((r[key] for r in out["control"] if key in r),
                           default=None),
            "readings": {p: min((r["readings"][p][key] for r in
                                 out["control"] if key in r["readings"][p]),
                                default=None)
                         for p in (TRAIN_READINGS if kind == "train"
                                   and out["control"] else ())},
            "faults": {n: min((r[key] for r in rs), default=None)
                       for n, rs in out["faults"].items()}}
    out["summary"] = summary
    print(json.dumps(summary, indent=1), flush=True)
    save()
    return 0


def _plain(x):
    return json.loads(json.dumps(x, default=str)) if x is not None else None


if __name__ == "__main__":
    sys.exit(main())
