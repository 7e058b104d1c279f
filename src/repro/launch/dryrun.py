import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count at first init).  Do not move them.

"""Multi-pod dry-run CLI (assignment deliverable e).

For every (architecture × input shape × mesh) cell:
    lower → compile → memory_analysis / cost_analysis / collective bytes,
on the 16×16 single-pod mesh and the 2×16×16 multi-pod mesh, using
ShapeDtypeStruct inputs only (no allocation).  The machinery lives in
:mod:`repro.api.aot` (public); this module is the CLI + the env hook
that forces the 512 host devices before jax initializes.

Usage:
    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    python -m repro.launch.dryrun --all --out results/dryrun
    python -m repro.launch.dryrun --all --multi-pod --out results/dryrun
"""
import argparse
import json

from repro.api.aot import HBM_BW, LINK_BW, PEAK_FLOPS, run_cell  # noqa: F401
from repro.compile_cache import enable_compile_cache
from repro.configs.base import SHAPES
from repro.configs.registry import ARCH_IDS


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--flash", action="store_true")
    ap.add_argument("--sharded-accum", action="store_true")
    ap.add_argument("--kv-repeat", action="store_true")
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "save_block_outputs"])
    ap.add_argument("--mode", default="2d", choices=["2d", "dp_only"])
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-parallel activation anchors on the "
                         "pjit path: the inter-block activations pin "
                         "the seq dim (not the feature dim) to 'model' "
                         "— GSPMD lowers the TP all-reduces as "
                         "reduce-scatter/all-gather pairs")
    ap.add_argument("--moe-ep", default="model", choices=["model", "data"])
    ap.add_argument("--microbatch", type=int, default=32)
    ap.add_argument("--out", default="")
    ap.add_argument("--tag", default="",
                    help="suffix for the output json (perf variants)")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("need --arch and --shape (or --all)")
        cells = [(args.arch, args.shape)]

    results = []
    for a, s in cells:
        rec = run_cell(
            a, s, multi_pod=args.multi_pod, fsdp=not args.no_fsdp,
            microbatch=args.microbatch, remat=not args.no_remat,
            flash=args.flash, sharded_accum=args.sharded_accum,
            kv_repeat=args.kv_repeat, remat_policy=args.remat_policy,
            mode=args.mode, moe_ep_axis=args.moe_ep,
            seq_shard=args.seq_shard,
        )
        results.append(rec)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            tag = "multi" if args.multi_pod else "single"
            if args.tag:
                tag += "__" + args.tag
            path = os.path.join(args.out, f"{a}__{s}__{tag}.json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} failed "
          f"of {len(results)} cells")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
