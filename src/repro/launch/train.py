"""Fault-tolerant HGC training driver CLI.

Thin front-end over the public object model (:mod:`repro.api`): flags →
``CodedCluster`` + planner strategy + ``CodedSession`` → ``fit()``.
The three ``--dist`` aggregation modes are session policies:

  * ``off`` — single-host reference loop: λ rides the per-example batch
    weights (coeff × λ) and the jit gradient reduction decodes the coded
    aggregate implicitly,
  * ``coded`` — mesh-aware loop on a (pod, data[, model]) device mesh
    with the two-stage coded decode (eqs. 25/27) as real shard_map
    collectives, λ as a runtime operand (drops/replans never recompile),
  * ``coded_int8`` — same, with the bandwidth-limited edge→master hop
    quantized to blockwise int8 + error feedback.

Common to all modes: JNCSS plans the coding scheme from the cluster
model (or --s_e/--s_w fixes it); every iteration simulates/observes the
straggler pattern; checkpoints are atomic and carry the data-iterator
state, the straggler detector's EWMA buffers, the deployed (tolerance,
K) and the EF residuals — a killed-and-resumed run replans from
*observed* delays and reproduces the uninterrupted run bit-for-bit.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
      --steps 50 --scheme hgc_jncss
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
      --steps 4 --dist coded_int8
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.api import CodedCluster, CodedSession, planner_for_scheme
# back-compat re-exports: these moved to repro.api (tests and user code
# imported them from here)
from repro.api.cluster import sample_straggler_pattern as \
    _sample_straggler_pattern_impl
from repro.api.session import (  # noqa: F401
    _extend_streams,
    _step_rng,
    build_coded_batch,
)
from repro.compile_cache import enable_compile_cache
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.core.topology import Topology
from repro.launch.steps import _warn_once


def _sample_straggler_pattern(rng, code, params, D):
    """Back-compat alias of :func:`repro.api.sample_straggler_pattern`."""
    return _sample_straggler_pattern_impl(rng, code, params, D)


def _make_cluster(kind: str, topo: Topology):
    """Deprecated — use :meth:`repro.api.CodedCluster.homogeneous` /
    :meth:`~repro.api.CodedCluster.hetero` (this shim returns the bare
    ``ClusterParams`` those constructors wrap)."""
    _warn_once("train._make_cluster",
               "repro.api.CodedCluster.homogeneous / .hetero")
    ctor = CodedCluster.hetero if kind == "hetero" \
        else CodedCluster.homogeneous
    return ctor(topo=topo).params


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b",
                    choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--part-batch", type=int, default=1,
                    help="examples per dataset part per iteration")
    ap.add_argument("--scheme", default="hgc_jncss",
                    choices=["hgc", "hgc_jncss", "uncoded",
                             "hgc_grouped", "hgc_comm"],
                    help="planning strategy (see docs/planners.md): "
                         "hgc_jncss=Algorithm 2, hgc=fixed (s_e,s_w), "
                         "uncoded=no redundancy, hgc_grouped=per-edge "
                         "worker tolerances, hgc_comm=message-budgeted")
    ap.add_argument("--s-e", type=int, default=1)
    ap.add_argument("--s-w", type=int, default=1)
    ap.add_argument("--n-edges", type=int, default=2)
    ap.add_argument("--n-workers", type=int, default=4)
    ap.add_argument("--cluster", default="homogeneous",
                    choices=["homogeneous", "hetero"],
                    help="simulated cluster model (hetero: one slow "
                         "edge — JNCSS then plans real edge tolerance)")
    ap.add_argument("--K", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--dist", default="off",
                    choices=["off", "coded", "coded_int8", "coded_q"],
                    help="aggregation execution mode: single-host "
                         "reference, shard_map coded collectives, "
                         "coded with the int8+EF cross-pod hop, or "
                         "coded_q with the codec --grad-compression "
                         "picks (int8 | int4 | fp8)")
    ap.add_argument("--model-shards", type=int, default=1,
                    help="'model' mesh axis size (--dist modes): real "
                         "in-shard_map tensor parallelism — params/opt-"
                         "state shard over it AND the forward/backward "
                         "runs Megatron-style column/row-parallel with "
                         "psums over 'model'")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel degree override (0 = use "
                         "--model-shards).  Validated against the arch "
                         "config's divisibility constraints up front — "
                         "a clear error instead of a shape crash")
    ap.add_argument("--seq-shard", dest="seq_shard",
                    action="store_const", const=True, default=None,
                    help="sequence parallelism inside the dist-TP "
                         "shard_map: activations between the TP "
                         "collective pairs shard over 'model' along "
                         "seq (reduce-scatter/all-gather instead of "
                         "all-reduce — tp x less activation state at "
                         "identical collective bytes).  Needs --tp > 1 "
                         "and seq-len divisible by tp; composes with "
                         "--dist coded_int8.  Default: the "
                         "TrainConfig.seq_shard_activations config "
                         "value")
    ap.add_argument("--no-seq-shard", dest="seq_shard",
                    action="store_const", const=False,
                    help="force sequence parallelism off (overrides "
                         "the config-level default)")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel stage count (--dist modes): "
                         "a leading 'stage' mesh axis shards the "
                         "stacked layer groups and the train step runs "
                         "a microbatched ppermute pipeline inside the "
                         "same shard_map as the coded decode.  Needs "
                         "n_layers//len(block_pattern) divisible by "
                         "the stage count; composes with --tp, "
                         "--seq-shard and --dist coded_int8")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="pipeline microbatch count per step (0 = one "
                         "per stage, the minimum that fills the "
                         "pipeline).  Must divide the per-group coded "
                         "batch rows (load D × --part-batch)")
    ap.add_argument("--grad-block", type=int, default=64,
                    help="quantization block size on the edge→master "
                         "hop (any codec)")
    ap.add_argument("--grad-compression", default="",
                    choices=["", "int8", "int4", "fp8"],
                    help="cross-pod codec for --dist coded_q "
                         "(default int8): int8/fp8 cut the hop bytes "
                         "4x, packed int4 8x; all share the EF "
                         "residual contract, so kill/resume and "
                         "replans behave identically")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--stop-after", type=int, default=0,
                    help="simulate a kill: exit cleanly after N steps "
                         "without touching the LR schedule (--steps "
                         "still sets total_steps, so a later --resume "
                         "run reproduces the uninterrupted trajectory)")
    ap.add_argument("--replan-every", type=int, default=0,
                    help="re-run JNCSS from observed delays every N steps")
    ap.add_argument("--force-drop-edge", type=int, default=-1,
                    help="force this edge to straggle at --force-drop-step")
    ap.add_argument("--force-drop-step", type=int, default=-1)
    ap.add_argument("--metrics-out", default="",
                    help="write per-step losses + jit cache stats as JSON")
    ap.add_argument("--expect-zero-recompile", action="store_true",
                    help="exit 1 if the train step compiled more than once")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    tp = args.tp or args.model_shards
    if args.dist == "off" and tp > 1:
        raise SystemExit("--tp requires a --dist mode (the single-host "
                         "reference loop has no model mesh axis)")
    if args.dist == "off" and args.pp > 1:
        raise SystemExit("--pp requires a --dist mode (the pipeline "
                         "runs over the 'stage' mesh axis inside "
                         "shard_map)")
    ctor = CodedCluster.hetero if args.cluster == "hetero" \
        else CodedCluster.homogeneous
    try:
        session = CodedSession(
            ctor(args.n_edges, args.n_workers),
            cfg,
            planner=planner_for_scheme(args.scheme, args.s_e, args.s_w),
            mode=args.dist,
            tp=tp,
            seq_shard=args.seq_shard,
            pp=args.pp,
            microbatches=args.microbatches,
            seq_len=args.seq_len,
            part_batch=args.part_batch,
            K=args.K,
            optimizer=args.optimizer,
            lr=args.lr,
            total_steps=args.steps,
            grad_block=args.grad_block,
            grad_compression=args.grad_compression,
            seed=args.seed,
            scheme=args.scheme,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            resume=args.resume,
            log_every=args.log_every,
        )
    except ValueError as e:
        raise SystemExit(f"[train] {e}")
    report = session.fit(
        args.steps,
        replan_every=args.replan_every,
        force_drop_edge=args.force_drop_edge,
        force_drop_step=args.force_drop_step,
        stop_after=args.stop_after,
    )
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(report, f, indent=1)
    if args.expect_zero_recompile:
        cache_entries = report["jit_cache_entries"]
        if cache_entries != 1:
            print(f"[train] FAIL: expected exactly 1 jit cache entry "
                  f"(zero recompiles), found {cache_entries}",
                  file=sys.stderr)
            sys.exit(1)
    return session.params


if __name__ == "__main__":
    main()
