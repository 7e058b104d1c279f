"""`CodedSession` — the deployed coded system as one object.

The paper's system is a hierarchical cluster with a deployed code, a
runtime model and an elastic replanning loop; this class owns all of it:
the device mesh and sharded training state, the compiled train / eval /
prefill / decode steps, the per-part data streams, the straggler
simulation + detector feedback, JNCSS replanning, permanent-failure
shrinking, and the checkpoint round trip (bit-for-bit kill/resume).

The aggregation policies of the train CLI map to ``mode``:

  * ``"off"``        — single-host reference: λ rides the per-example
    batch weights and the jit gradient reduction decodes implicitly,
  * ``"coded"``      — (pod, data[, model]) mesh, two-stage coded
    shard_map decode with λ as a runtime operand (zero recompiles
    across straggler drops and replans),
  * ``"coded_int8"`` — same, with the blockwise-int8 + error-feedback
    edge→master hop (per-pod EF residuals ride the training state),
  * ``"coded_q"``    — same hop with the codec ``grad_compression``
    selects (int8 default, int4 packed nibbles, or fp8-e4m3) — all
    three share the f32 EF-residual contract, so checkpoints,
    kill/resume, and replans behave identically across codecs.

Quickstart::

    from repro.api import CodedCluster, CodedSession
    from repro.configs.registry import get_smoke_config

    cluster = CodedCluster.hetero(n_edges=2, n_workers=4)
    session = CodedSession(cluster, get_smoke_config("llama3-8b"),
                           planner="jncss", total_steps=20)
    session.fit()
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.api import serving
from repro.api.cluster import CodedCluster, sample_straggler_pattern
from repro.api.planner import Planner, get_planner
from repro.checkpoint.store import CheckpointStore, config_hash
from repro.configs.base import ModelConfig, TrainConfig
from repro.core.hgc import HGCCode
from repro.core.topology import Tolerance
from repro.dist.elastic import Plan, price_tolerance
from repro.data.pipeline import TokenStream
from repro.models import transformer as tf
from repro.optim import make_optimizer

PyTree = Any

tracing.install_gc_spans()


class ReplanError(RuntimeError):
    """A replan/shrink produced a plan the deployed session cannot run.

    Raised INSTEAD of adopting the offending plan — the session keeps
    training on its previous code, so a supervisor (the orchestrator)
    can log the failure and keep the episode alive.  Structured fields:

      * ``constraint`` — which deployment constraint broke:
        ``"uniform_load"`` (grouped per-edge loads under a dist mode),
        ``"pp"`` (pipeline row/stage divisibility for the new load D),
        or ``"topology"`` (a supplied cluster's tree does not match),
      * ``topo`` — the surviving :class:`Topology` the plan was for.
    """

    def __init__(self, message: str, *, constraint: str, topo):
        super().__init__(message)
        self.constraint = constraint
        self.topo = topo


def _step_rng(seed: int, step: int) -> np.random.Generator:
    """Per-step straggler RNG: resume replays the exact pattern sequence
    (bit-for-bit kill/resume needs history-independent sampling)."""
    return np.random.default_rng(np.random.SeedSequence([seed, 7919, step]))


def _code_desc(code) -> Dict:
    """The checkpointed code descriptor: enough to rebuild the deployed
    code deterministically (grouped codes add their per-edge vector)."""
    d = {"s_e": code.tol.s_e, "s_w": code.tol.s_w, "K": code.K}
    vec = getattr(code.tol, "s_w_vec", None)
    if vec is not None:
        d["s_w_vec"] = [int(s) for s in vec]
    return d


def build_coded_batch(code: HGCCode, streams, fast_e, fast_w, seq_len,
                      with_lam: bool = True):
    """Global batch = all workers' assigned-part examples.

    ``with_lam=True`` (single-host path): weights carry coeff × λ so the
    jit gradient reduction decodes implicitly; straggling workers get
    weight 0 (their rows still flow through the step fn — shapes are
    static, only weights change).  ``with_lam=False`` (``--dist``
    paths): weights carry the coding coefficients only — λ is applied
    inside the shard_map decode, per shard group.  Example order is
    (pod, data)-major either way, so sharding the batch dim over
    ("pod", "data") hands worker (i, j) exactly its own examples.
    """
    lam = code.collapsed_weights(fast_e, fast_w) if with_lam else None
    tokens, targets, weights = [], [], []
    topo = code.topo
    for i in range(topo.n):
        for j in range(topo.m[i]):
            w_idx = topo.flat_index(i, j)
            coeff = code.worker_coeffs(i, j)
            for k in code.assignment.worker_parts(i, j):
                b = streams[k].next_batch()
                tokens.append(b["tokens"])
                targets.append(b["targets"])
                w = b["weights"] * float(coeff[k])
                if lam is not None:
                    w = w * float(lam[w_idx])
                weights.append(w)
    return {
        "tokens": np.concatenate(tokens, 0),
        "targets": np.concatenate(targets, 0),
        "weights": np.concatenate(weights, 0),
        # fixed normalizer keeps the loss linear in the weights (exact
        # coded decode); K parts × per-part token count
        "denom": np.float32(
            code.K * tokens[0].shape[0] * seq_len
        ),
    }


def _extend_streams(streams, K: int, vocab: int, part_batch: int,
                    seq_len: int, seed: int):
    """K growth (replan / restored checkpoint) REUSES the existing part
    streams — only the new parts get fresh resumable streams."""
    while len(streams) < K:
        streams.append(
            TokenStream(vocab, part_batch, seq_len,
                        seed=seed * 1000 + len(streams))
        )


class CodedSession:
    """One coded train/serve session over a :class:`CodedCluster`.

    ``cluster=None`` builds a serve-only session (no planning, no data
    streams, no train step) — the serving driver's mode.

    ``cfg.param_dtype`` is the dtype of the weights a session trains
    (its master copy, cast to ``cfg.dtype`` inside every step).  A
    serve-only session never updates its weights, so it holds them in
    the compute dtype ``cfg.dtype``, cast once at construction.
    """

    def __init__(
        self,
        cluster: Optional[CodedCluster],
        cfg: ModelConfig,
        *,
        planner: Any = "jncss",
        mode: str = "off",
        tp: int = 1,
        seq_shard: Optional[bool] = None,
        pp: int = 1,
        microbatches: int = 0,
        seq_len: int = 64,
        part_batch: int = 1,
        K: int = 0,
        optimizer: str = "adamw",
        lr: float = 1e-2,
        total_steps: int = 100,
        warmup_steps: Optional[int] = None,
        grad_clip: float = 1.0,
        grad_block: int = 64,
        grad_compression: str = "",
        seed: int = 0,
        scheme: Optional[str] = None,
        checkpoint_dir: str = "",
        checkpoint_every: int = 25,
        keep_checkpoints: int = 3,
        resume: bool = False,
        log_every: int = 10,
        verbose: bool = True,
    ):
        if mode not in ("off", "coded", "coded_int8", "coded_q"):
            raise ValueError(f"unknown session mode {mode!r}")
        # codec for the compressed cross-pod hop: "coded_int8" pins
        # int8 (back-compat spelling); "coded_q" reads grad_compression
        # (default int8, or int4 / fp8 — see dist/compression.py)
        if mode == "coded_int8":
            if grad_compression and grad_compression != "int8":
                raise ValueError(
                    "mode='coded_int8' pins grad_compression='int8'; "
                    "use mode='coded_q' to pick a codec"
                )
            self.grad_compression = "int8"
        elif mode == "coded_q":
            self.grad_compression = grad_compression or "int8"
            from repro.dist import compression as _comp

            if self.grad_compression not in _comp.COMPRESSION_MODES:
                raise ValueError(
                    f"unknown grad_compression "
                    f"{self.grad_compression!r} (choose from "
                    f"{_comp.COMPRESSION_MODES})"
                )
        else:
            if grad_compression:
                raise ValueError(
                    f"grad_compression={grad_compression!r} needs "
                    "mode='coded_q' (or 'coded_int8')"
                )
            self.grad_compression = "none"
        self.cluster = cluster
        self.cfg = cfg
        self.mode = mode
        self.tp = max(int(tp), 1)
        # --seq-shard precedence: an explicit flag (True/False) wins;
        # None falls back to the TrainConfig-level default.  A config-
        # level True quietly stays off where SP cannot apply (tp <= 1 /
        # mode off); an EXPLICIT True there is a flag error instead.
        self._seq_shard_explicit = seq_shard is not None
        self.seq_shard = bool(
            seq_shard if seq_shard is not None
            else TrainConfig.__dataclass_fields__[
                "seq_shard_activations"].default
        )
        self.pp = max(int(pp), 1)
        self.microbatches = max(int(microbatches), 0)
        if self.microbatches and self.pp <= 1:
            raise ValueError(
                "microbatches requires pp > 1 (the pipeline microbatch "
                "count only applies to the stage pipeline; the "
                "single-host accumulation knob is TrainConfig.microbatch)"
            )
        self.seq_len = seq_len
        self.part_batch = part_batch
        self.seed = seed
        self.log_every = log_every
        self.verbose = verbose
        self.losses: List[float] = []
        #: steps run, decoded and dispatched (coded) tokens, and workers
        #: with λ = 0 summed over steps
        self.counters = dict.fromkeys(
            ("steps", "useful_tokens", "coded_tokens", "dropped_workers"), 0)
        self._serve_cache: Dict = {}
        self._eval_fn = None

        # model state (shared by train and serve paths)
        rng = jax.random.PRNGKey(seed)
        if cluster is None:  # serve-only session: no optimizer, no plan
            # the weights never change, so hold the copy prefill and
            # decode compute in (their cast_params is then a no-op), made
            # in one jit so the float32 tree is never held beside it
            self.params = jax.jit(
                lambda k: tf.cast_params(tf.init_params(k, cfg), cfg))(rng)
            self.plan = None
            self.code = None
            self.tcfg = None
            self._optimizer = None
            self.opt_state = None
            self.store = None
            self._step = 0
            self._mesh = None
            return
        self.params = tf.init_params(rng, cfg)
        self._optimizer = make_optimizer(optimizer)

        # ---- plan the code ------------------------------------------
        self.planner: Planner = get_planner(planner)
        topo = cluster.topo
        K_target = K or self.planner.initial_K(topo)
        self.plan = self.planner.plan(cluster.params, K_target, seed=seed)
        self.code = self.plan.code
        self.scheme = scheme or (
            "hgc_jncss" if self.plan.jncss is not None else "hgc"
        )
        if self.verbose:
            if self.plan.jncss is not None:
                print(f"[train] JNCSS chose (s_e={self.code.tol.s_e}, "
                      f"s_w={self.code.tol.s_w}), D={self.code.load}, "
                      f"K={self.code.K}, "
                      f"T̂={self.plan.expected_iteration_ms:.0f} ms")
            else:
                print(f"[train] fixed scheme {self.scheme}: "
                      f"(s_e={self.code.tol.s_e}, "
                      f"s_w={self.code.tol.s_w}), D={self.code.load}, "
                      f"K={self.code.K}")

        self.tcfg = TrainConfig(
            optimizer=optimizer, lr=lr, total_steps=total_steps,
            warmup_steps=(warmup_steps if warmup_steps is not None
                          else max(total_steps // 10, 1)),
            grad_clip=grad_clip,
            scheme=self.scheme, s_e=self.code.tol.s_e,
            s_w=self.code.tol.s_w, K=self.code.K,
            dist_mode=mode,
            grad_compression=self.grad_compression,
            grad_compression_block=grad_block,
            seq_shard_activations=self.seq_shard,
            pp_stages=self.pp,
            microbatches=self.microbatches,
        )

        # ---- data: one resumable stream per dataset part -------------
        self.streams: List[TokenStream] = []
        _extend_streams(self.streams, self.code.K, cfg.vocab, part_batch,
                        seq_len, seed)

        # ---- init / resume -------------------------------------------
        self.opt_state = self._optimizer.init(self.params)
        self._step = 0
        self.store = None
        self._restored_extra: Dict = {}
        if checkpoint_dir:
            # hash the MODEL config only: run hyperparameters
            # (total_steps, lr schedule) legitimately change across
            # restarts
            self.store = CheckpointStore(
                checkpoint_dir, keep=keep_checkpoints,
                cfg_hash=config_hash(cfg),
            )
            if resume and self.store.latest_step() is not None:
                self._resume()
        self.checkpoint_every = checkpoint_every

        self._setup_train_step()

    # ------------------------------------------------------------------
    # restore
    # ------------------------------------------------------------------
    def _resume(self):
        start, state, extra = self.store.restore()
        self._restored_extra = extra
        self.params = jax.tree.map(jnp.asarray, state["params"])
        if "opt_state" in state:
            # stateless optimizers (sgd) flatten to an empty subtree —
            # the freshly initialized opt_state is already correct then
            self.opt_state = jax.tree.map(jnp.asarray,
                                          state["opt_state"])
        cl = extra.get("cluster")
        if cl and (cl.get("dead_edges") or cl.get("dead_workers")):
            # the run had shrunk past permanent failures before the
            # kill — rebuild the surviving cluster from the base model
            self.cluster = self.cluster.restored(cl)
            if self.verbose:
                print(f"[train] restored shrunk topology "
                      f"m={self.cluster.topo.m}")
        ck = extra.get("code")
        if ck and (
            ck != _code_desc(self.code)
            or self.code.topo != self.cluster.topo
        ):
            # the run had replanned before the kill — rebuild the
            # deployed code deterministically (same seed ⇒ same code)
            if "s_w_vec" in ck:
                from repro.core.grouping import (
                    GroupedHGCCode, GroupTolerance, price_grouped,
                )

                self.code = GroupedHGCCode.build(
                    self.cluster.topo,
                    GroupTolerance(ck["s_e"], tuple(ck["s_w_vec"])),
                    K=ck["K"], seed=self.seed,
                )
                priced = price_grouped(
                    self.cluster.params, self.code.tol, self.code.loads
                )
            else:
                self.code = HGCCode.build(
                    self.cluster.topo, Tolerance(ck["s_e"], ck["s_w"]),
                    K=ck["K"], seed=self.seed,
                    construction=getattr(self.planner, "construction",
                                         "random"),
                )
                priced = price_tolerance(
                    self.cluster.params, self.code.tol, self.code.load
                )
            # keep the plan (the public λ provider) in lockstep with
            # the actually deployed code
            self.plan = Plan(
                code=self.code, tol=self.code.tol, K=self.code.K,
                expected_iteration_ms=priced,
                jncss=None,
            )
            if self.verbose:
                print(f"[train] restored replanned code "
                      f"(s_e={ck['s_e']}, s_w={ck['s_w']}, K={ck['K']})")
        saved_streams = extra["streams"]
        # the saved list may exceed code.K (a replan once grew K and
        # later shrank it — streams are never discarded)
        _extend_streams(self.streams,
                        max(self.code.K, len(saved_streams)),
                        self.cfg.vocab, self.part_batch, self.seq_len,
                        self.seed)
        for k, sd in enumerate(saved_streams):
            self.streams[k].load_state_dict(sd)
        if "detector" in extra:
            self.cluster.detector.load_state_dict(extra["detector"])
        self._step = start
        if self.verbose:
            print(f"[train] resumed from step {start}")

    # ------------------------------------------------------------------
    # step compilation (mesh, shardings, λ / EF residuals)
    # ------------------------------------------------------------------
    def _setup_train_step(self):
        """Jit the train step; in the dist modes build the mesh, shard
        the state onto it and PIN the output shardings — outputs land in
        exactly the input layouts, so step 2 reuses step 1's executable
        (the zero-recompile invariant)."""
        from repro.launch import steps as steps_lib

        topo = self.cluster.topo
        # a rebuild after shrink() carries the surviving pods' EF
        # residual rows through; the first build starts empty
        carry_residual = getattr(self, "residual", {}) or {}
        self.residual: Dict = {}
        self._batch_sh = self._lam_sh = None
        if self.mode == "off":
            self._mesh = None
            if self.tp > 1:
                raise ValueError(
                    "tp > 1 requires a dist mode (the single-host "
                    "reference loop has no model mesh axis)"
                )
            if self.seq_shard and self._seq_shard_explicit:
                raise ValueError(
                    "--seq-shard requires a dist mode (sequence "
                    "sharding rides the 'model' mesh axis)"
                )
            if self.pp > 1:
                raise ValueError(
                    "pp > 1 requires a dist mode (the pipeline runs "
                    "over the 'stage' mesh axis inside shard_map)"
                )
            self.train_step = jax.jit(
                steps_lib.make_train_step(self.cfg, self.tcfg,
                                          optimizer=self._optimizer)
            )
            return

        if len(set(topo.m)) != 1:
            raise ValueError(
                f"dist modes need a uniform topology for the "
                f"(pod, data) mesh, got m={topo.m}"
            )
        self._require_dist_uniform_load(self.code)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.dist import compression as comp_lib
        from repro.dist import sharding as shard_lib
        from repro.dist.mesh import make_test_mesh

        pods, data = topo.n, topo.m[0]
        shard_lib.validate_tp(self.cfg, self.tp)
        if self.seq_shard and (self.tp > 1 or self._seq_shard_explicit):
            # validate_tp-style clear errors: tp>1 requirement +
            # seq % tp divisibility (+ the recurrent fallback warning)
            shard_lib.validate_seq_shard(self.cfg, self.tp, self.seq_len)
        self._validate_pp(self.code)
        mesh = self._mesh = make_test_mesh(pods, data, self.tp,
                                           stages=self.pp)
        if self.verbose:
            print(f"[train] dist={self.mode}: mesh "
                  + (f"(stage={self.pp} × " if self.pp > 1 else "(")
                  + f"pod={pods} × data={data} × "
                  f"model={self.tp}), "
                  f"grad_compression={self.tcfg.grad_compression}"
                  + (f", TP degree {self.tp}" if self.tp > 1 else "")
                  + (", seq-parallel activations"
                     if self.seq_shard and self.tp > 1 else "")
                  + (f", pipeline stages {self.pp} × "
                     f"{self.microbatches or self.pp} microbatches"
                     if self.pp > 1 else ""))

        param_sh, opt_sh = shard_lib.state_shardings(
            self.params, self.opt_state, self.cfg, mesh,
            fsdp=self.tcfg.fsdp, head_aligned=True,
        )
        self.params = jax.device_put(self.params, param_sh)
        self.opt_state = jax.device_put(self.opt_state, opt_sh)
        dp = ("pod", "data")
        self._batch_sh = {
            "tokens": NamedSharding(mesh, P(dp, None)),
            "targets": NamedSharding(mesh, P(dp, None)),
            "weights": NamedSharding(mesh, P(dp, None)),
            "denom": NamedSharding(mesh, P()),
        }
        self._lam_sh = NamedSharding(mesh, P("pod", "data"))
        res_sh: Dict = {}
        if self.tcfg.grad_compression != "none":
            if carry_residual:
                self.residual = jax.tree.map(jnp.asarray, carry_residual)
            elif "ef_residual" in self._restored_extra:
                # consume the checkpoint payload: a later mesh rebuild
                # must carry the LIVE residual, not roll back to this
                self.residual = jax.tree.map(
                    jnp.asarray, self._restored_extra.pop("ef_residual")
                )
            else:
                self.residual = comp_lib.init_pod_residuals(
                    self.params, pods
                )
            # under TP the residual follows its gradient leaf onto the
            # model axis (same pspec rules as the step's shard_map)
            res_sh = shard_lib.to_shardings(
                shard_lib.residual_pspecs(self.params, self.cfg, mesh,
                                          fsdp=self.tcfg.fsdp),
                mesh,
            )
            self.residual = jax.device_put(self.residual, res_sh)
        self.train_step = jax.jit(
            steps_lib._make_dist_train_step(self.cfg, self.tcfg, mesh,
                                            optimizer=self._optimizer),
            out_shardings=(param_sh, opt_sh, res_sh,
                           NamedSharding(mesh, P())),
        )

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------
    def build_batch(self, fast_e, fast_w):
        """The coded global batch for one observed straggler pattern."""
        return build_coded_batch(
            self.code, self.streams, fast_e, fast_w, self.seq_len,
            with_lam=(self._mesh is None),
        )

    def _validate_pp(self, code):
        """Clear pp errors up front: group count % stages AND the
        per-group coded batch rows % microbatches.  Re-checked on every
        replan/shrink — a new code's load D changes the row count."""
        if self.pp <= 1:
            return
        from repro.dist import sharding as shard_lib

        loads = getattr(code, "loads", None)
        load = int(loads[0]) if loads else int(code.load)
        shard_lib.validate_pp(
            self.cfg, self.pp,
            microbatches=self.microbatches or self.pp,
            batch_rows=load * self.part_batch,
        )

    def _require_dist_uniform_load(self, code):
        """Dist modes shard the batch dim evenly over (pod, data) — a
        grouped code whose edges carry different loads would misalign
        batch rows with workers.  Uniform-valued grouped plans pass."""
        if self.mode == "off":
            return
        loads = getattr(code, "loads", None)
        if loads is not None and len(set(loads)) > 1:
            counts: Dict[int, int] = {}
            for d in loads:
                counts[int(d)] = counts.get(int(d), 0) + 1
            majority = max(counts, key=lambda d: (counts[d], -d))
            edge, load = next(
                (i, int(d)) for i, d in enumerate(loads)
                if int(d) != majority
            )
            raise ValueError(
                f"dist mode {self.mode!r} shards the coded batch evenly "
                f"over the (pod, data) mesh, which requires every worker "
                f"to carry the same load — but this grouped plan gives "
                f"edge {edge} load D={load} while the majority of edges "
                f"carry D={majority} (per-edge loads: {tuple(loads)}). "
                f"Use a uniform planner, regroup the cluster so loads "
                f"match, or run mode='off'; see docs/planners.md "
                f"(grouped codes under dist modes)"
            )

    def _iteration(self, step: int, force_drop_edge: int = -1,
                   force_drop_step: int = -1, batch=None) -> Dict:
        code, topo = self.code, self.cluster.topo
        with tracing.step_span(step) as span:
            with tracing.span("train.pattern"):
                fast_e, fast_w, t_iter, wt = sample_straggler_pattern(
                    _step_rng(self.seed, step), code, self.cluster.params,
                    getattr(code, "load_array", code.load),
                )
                if step == force_drop_step and \
                        0 <= force_drop_edge < topo.n and code.tol.s_e > 0:
                    # forced straggler drop: exercise the zero-recompile
                    # claim — only the λ operand changes, never the
                    # compiled step
                    fast_e = tuple(
                        i for i in range(topo.n) if i != force_drop_edge
                    )[: topo.n - code.tol.s_e]
                self.cluster.observe(wt)
            metrics = self._execute(step, fast_e, fast_w, batch, span)
        metrics["sim_iter_ms"] = t_iter
        metrics["fast_edges"] = fast_e
        return metrics

    def _execute(self, step: int, fast_e, fast_w, batch, span) -> Dict:
        """Dispatch ONE compiled train step under a given completion
        set — the shared tail of :meth:`_iteration` (simulated patterns)
        and :meth:`external_step` (orchestrator-observed patterns).
        ``span`` is the step's open ``train.step`` span."""
        code, topo = self.code, self.cluster.topo
        lam = code.collapsed_weights(fast_e, fast_w)
        dropped = int(np.count_nonzero(lam == 0))
        span.set_metadata(dropped_edges=topo.n - len(set(fast_e)),
                          dropped_workers=dropped)
        if batch is None:
            with tracing.span("train.build_batch"):
                batch = self.build_batch(fast_e, fast_w)
        rows, seq = np.shape(batch["tokens"])
        with tracing.span("train.put", rows=rows):
            if self._mesh is None:
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
            else:
                batch = {
                    k: jax.device_put(jnp.asarray(v), self._batch_sh[k])
                    for k, v in batch.items()
                }
                lam_arr = jax.device_put(
                    jnp.asarray(lam.reshape(topo.n, topo.m[0]),
                                jnp.float32),
                    self._lam_sh,
                )
        with tracing.span("train.dispatch"):
            if self._mesh is None:
                self.params, self.opt_state, metrics = self.train_step(
                    self.params, self.opt_state, batch, jnp.asarray(step)
                )
            else:
                (self.params, self.opt_state, self.residual,
                 metrics) = self.train_step(
                    self.params, self.opt_state, batch, lam_arr,
                    self.residual, jnp.asarray(step),
                )
        with tracing.span("train.loss_sync"):
            self.losses.append(float(metrics["loss"]))
        self._step = step + 1
        c = self.counters
        c["steps"] += 1
        c["useful_tokens"] += code.K * self.part_batch * self.seq_len
        c["coded_tokens"] += rows * seq
        c["dropped_workers"] += dropped
        return dict(metrics)

    def external_step(self, fast_e, fast_w, *, worker_totals=None,
                      sim_iter_ms: float = 0.0, batch=None) -> Dict:
        """One train step under an EXTERNALLY-observed completion set.

        The orchestrator's entry point: instead of *simulating* a
        straggler pattern from the cluster model (:meth:`step`), the
        caller supplies the completion set it actually waited for —
        ``fast_e`` (edge indices) and ``fast_w`` (per-edge fast-worker
        tuples, indexed by edge for ALL edges) — plus, optionally, the
        flat per-worker runtime observations to feed the detector.
        Identical coded semantics: only the λ operand changes, so the
        compiled step is reused (zero recompiles), and replaying the
        same completion sets into a fresh session reproduces the same
        losses bit-for-bit.
        """
        if self.cluster is None:
            raise RuntimeError("serve-only session (cluster=None) "
                               "cannot train")
        topo = self.cluster.topo
        need_e = topo.n - self.code.tol.s_e
        if len(set(fast_e)) < need_e:
            raise ValueError(
                f"completion set has {len(set(fast_e))} edges; the "
                f"deployed code needs >= {need_e}"
            )
        for i in fast_e:
            need_w = topo.m[i] - self.code.tol.s_w_of(i)
            if len(set(fast_w[i])) < need_w:
                raise ValueError(
                    f"edge {i}: completion set has "
                    f"{len(set(fast_w[i]))} workers; the deployed code "
                    f"needs >= {need_w}"
                )
        with tracing.step_span(self._step) as span:
            if worker_totals is not None:
                with tracing.span("train.pattern"):
                    self.cluster.observe(worker_totals)
            metrics = self._execute(self._step, tuple(fast_e),
                                    [tuple(w) for w in fast_w], batch,
                                    span)
        metrics["sim_iter_ms"] = float(sim_iter_ms)
        metrics["fast_edges"] = tuple(fast_e)
        return metrics

    def step(self, batch=None) -> Dict:
        """One training iteration at the session's current step index.

        Samples a straggler pattern from the cluster model, feeds the
        detector, and runs the compiled step.  ``batch`` overrides the
        coded batch built from the session's part streams — it must be
        in the coded layout of :func:`build_coded_batch`.
        """
        if self.cluster is None:
            raise RuntimeError("serve-only session (cluster=None) "
                               "cannot train")
        return self._iteration(self._step, batch=batch)

    def fit(
        self,
        steps: Optional[int] = None,
        *,
        replan_every: int = 0,
        force_drop_edge: int = -1,
        force_drop_step: int = -1,
        stop_after: int = 0,
    ) -> Dict:
        """The managed loop: straggler simulation → coded step →
        detector feedback → elastic replan → checkpoint.

        ``steps`` is the GLOBAL target step (defaults to the LR
        schedule's ``total_steps``); a resumed session continues from
        its restored step.  ``stop_after`` simulates a kill: exit
        cleanly after N total steps without touching the LR schedule.
        Returns the metrics report (per-step losses + jit cache stats).
        """
        if self.cluster is None:
            raise RuntimeError("serve-only session (cluster=None) "
                               "cannot train")
        total = steps if steps is not None else self.tcfg.total_steps
        start = self._step
        t0 = time.time()
        sim_ms = 0.0
        steps_done = 0
        for step in range(start, total):
            steps_done += 1
            m = self._iteration(step, force_drop_edge, force_drop_step)
            sim_ms += m["sim_iter_ms"]
            if self.verbose and (
                    step % self.log_every == 0 or step == total - 1):
                topo = self.cluster.topo
                drop = sorted(set(range(topo.n)) - set(m["fast_edges"]))
                print(f"[train] step {step:5d} loss {self.losses[-1]:.4f} "
                      f"grad_norm {float(m['grad_norm']):.3f} "
                      f"sim_iter {m['sim_iter_ms']:.0f} ms "
                      f"stragglers: edges={drop}")
            if replan_every and (step + 1) % replan_every == 0:
                self.replan()
            # checkpoint AFTER a possible replan so the saved
            # (tolerance, K) is what the surviving run would train with
            if self.store and (step + 1) % self.checkpoint_every == 0:
                self.save_checkpoint(step + 1)
            if stop_after and step + 1 >= stop_after:
                if self.verbose:
                    print(f"[train] stopping after step {step} "
                          f"(simulated kill)")
                break
        cache_entries = self.jit_cache_entries()
        if self.verbose:
            wall = time.time() - t0
            print(f"[train] done: {steps_done} steps in {wall:.1f}s "
                  f"wall, {sim_ms/1e3:.1f}s simulated cluster time, "
                  f"jit cache entries: {cache_entries}")
        return self.report(first_step=start)

    def replan(self, planner: Any = None, cluster: Any = None):
        """Re-run the planner on the detector-updated cluster model;
        a stable plan reuses the deployed code and part streams.

        ``planner`` swaps the session's strategy first (string or
        instance, as in the constructor) — tolerance and λ are runtime
        operands, so a swap that lands on the same code shapes keeps
        the compiled step (zero recompiles).  ``cluster`` swaps the
        session's cluster model first — the orchestrator's fit-replan
        hook: hand in ``CodedCluster.from_observations(...)`` and the
        plan prices MEASURED delays instead of priors.  The swapped
        cluster must keep the deployed topology (a topology change is
        :meth:`shrink`, not a replan).

        A plan the deployed session cannot run (grouped loads under a
        dist mode, a pipeline-incompatible load) raises a structured
        :class:`ReplanError` and leaves the session on its previous
        plan."""
        if planner is not None:
            self.planner = get_planner(planner)
        if cluster is not None:
            if cluster.topo != self.cluster.topo:
                raise ReplanError(
                    f"replan cluster has topology m={cluster.topo.m}, "
                    f"session is deployed on m={self.cluster.topo.m} — "
                    f"use shrink() for topology changes",
                    constraint="topology", topo=self.cluster.topo,
                )
            self.cluster = cluster
        plan = self.planner.plan(
            self.cluster.updated_params(self.code.load), self.code.K,
            seed=self.seed, reuse=self.code,
        )
        if plan.code is not self.code:
            self._check_deployable(plan.code)
            if self.verbose:
                print(f"[train] replan: tolerance → "
                      f"(s_e={plan.tol.s_e}, s_w={plan.tol.s_w}), "
                      f"K={plan.K}, "
                      f"T̂={plan.expected_iteration_ms:.0f} ms")
            self.plan = plan
            self.code = plan.code
            # the compatible K for the new tolerance may exceed the old
            # one — existing part streams are reused, only the new
            # parts get streams
            _extend_streams(self.streams, self.code.K, self.cfg.vocab,
                            self.part_batch, self.seq_len, self.seed)
        return self.plan

    def _check_deployable(self, code) -> None:
        """Validate a REPLACEMENT code against the deployed session;
        failures surface as structured :class:`ReplanError` (the
        construction path keeps plain ``ValueError`` — there is no
        surviving plan to fall back to at construction time)."""
        try:
            self._require_dist_uniform_load(code)
        except ValueError as err:
            raise ReplanError(str(err), constraint="uniform_load",
                              topo=self.cluster.topo) from err
        try:
            self._validate_pp(code)
        except ValueError as err:
            raise ReplanError(str(err), constraint="pp",
                              topo=self.cluster.topo) from err

    def shrink(self, dead_edges=(), dead_workers=()):
        """Drop PERMANENTLY failed nodes, replan, and keep training.

        Transient stragglers need no action (the code tolerates them by
        construction); a permanent failure shrinks the cluster model,
        re-plans the tolerance on the survivors, and — in the dist
        modes — rebuilds the mesh and re-shards the (topology-
        independent) model state onto it.  One legitimate recompile;
        the shrink record rides checkpoints, so kill/resume replays the
        surviving cluster exactly.
        """
        old_topo = self.cluster.topo
        old_cluster = self.cluster
        keep = [i for i in range(old_topo.n) if i not in set(dead_edges)]
        self.cluster = self.cluster.shrink(dead_edges, dead_workers)
        try:
            plan = self.planner.plan(
                self.cluster.params, self.code.K, seed=self.seed,
            )
            self._check_deployable(plan.code)
        except ReplanError:
            self.cluster = old_cluster
            raise
        except ValueError as err:
            # the survivors cannot host ANY compatible plan (e.g. the
            # shrink made K incompatible with every tolerance level) —
            # keep the pre-shrink session intact and report what broke
            self.cluster = old_cluster
            raise ReplanError(
                str(err), constraint="plan",
                topo=old_cluster.shrink(dead_edges, dead_workers).topo,
            ) from err
        self.plan = plan
        self.code = self.plan.code
        _extend_streams(self.streams, self.code.K, self.cfg.vocab,
                        self.part_batch, self.seq_len, self.seed)
        if self.verbose:
            print(f"[train] shrink: topology → m={self.cluster.topo.m}, "
                  f"(s_e={self.code.tol.s_e}, s_w={self.code.tol.s_w}), "
                  f"K={self.code.K}")
        if self._mesh is not None:
            # surviving pods keep their own EF residual rows
            if self.residual:
                idx = np.asarray(keep, np.intp)
                self.residual = jax.tree.map(
                    lambda r: np.asarray(r)[idx], self.residual
                )
            self.params = jax.tree.map(np.asarray, self.params)
            self.opt_state = jax.tree.map(np.asarray, self.opt_state)
            self._setup_train_step()
        return self.plan

    # ------------------------------------------------------------------
    # checkpointing / reporting
    # ------------------------------------------------------------------
    def save_checkpoint(self, step: Optional[int] = None) -> str:
        if self.store is None:
            raise RuntimeError("session has no checkpoint_dir")
        # detector rides the top-level key only (the cluster snapshot
        # would duplicate it — one source of truth)
        cluster_state = self.cluster.state_dict()
        cluster_state.pop("detector", None)
        extra = {
            "streams": [s.state_dict() for s in self.streams],
            "detector": self.cluster.detector.state_dict(),
            "code": _code_desc(self.code),
            "cluster": cluster_state,
        }
        if self.tcfg.grad_compression != "none" and self._mesh is not None:
            extra["ef_residual"] = self.residual
        return self.store.save(
            self._step if step is None else step,
            {"params": self.params, "opt_state": self.opt_state},
            extra=extra,
        )

    def jit_cache_entries(self) -> int:
        """Compiled-executable count of the train step.  1 after a run
        == the zero-recompile invariant."""
        return int(self.train_step._cache_size())

    def report(self, first_step: int = 0) -> Dict:
        """The metrics payload the train CLI writes to --metrics-out."""
        return {
            "dist": self.mode,
            "first_step": first_step,
            "losses": self.losses,
            "jit_cache_entries": self.jit_cache_entries(),
            "counters": dict(self.counters),
        }

    # ------------------------------------------------------------------
    # evaluation
    # ------------------------------------------------------------------
    def eval_step(self, batch) -> Dict[str, float]:
        """Loss/metrics of one batch under the current params (no
        update, no coding — plain replicated evaluation)."""
        if self._eval_fn is None:
            cfg = self.cfg

            def eval_fn(params, batch):
                _, m = tf.loss_and_metrics(params, cfg, batch)
                return m

            self._eval_fn = jax.jit(eval_fn)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        return {k: float(v)
                for k, v in self._eval_fn(self.params, batch).items()}

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def _serve_fns(self, max_len: int, exact: bool):
        """Compiled (prefill, decode) pair; tensor-parallel when tp > 1.

        tp > 1 builds a serving mesh and pins in/out shardings from the
        SAME pspec rules training partitions from (`serve_shardings`) —
        GSPMD then runs the Megatron TP plan; the function bodies are
        the single-host ones, unchanged.
        """
        key = (max_len, exact, self.tp)
        if key in self._serve_cache:
            return self._serve_cache[key]
        prefill_raw = serving.make_prefill_fn(
            self.cfg, max_len, exact=exact
        )
        decode_raw = serving.make_decode_fn(self.cfg)
        if self.tp <= 1:
            entry = (jax.jit(prefill_raw), jax.jit(decode_raw), None)
        else:
            from repro.dist import sharding as shard_lib
            from repro.dist.mesh import make_serve_mesh

            shard_lib.validate_tp(self.cfg, self.tp)
            mesh = make_serve_mesh(self.tp)
            cache_abs = jax.eval_shape(
                lambda: tf.init_cache(self.cfg, 1, max_len,
                                      dtype="float32")
            )
            param_sh, cache_sh = shard_lib.serve_shardings(
                self.params, cache_abs, self.cfg, mesh
            )
            n_in = 3 if self.cfg.is_encdec else 2
            prefill = jax.jit(
                prefill_raw,
                in_shardings=(param_sh,) + (None,) * (n_in - 1),
                out_shardings=(None, cache_sh),
            )
            decode = jax.jit(
                decode_raw,
                in_shardings=(param_sh, None, cache_sh),
                out_shardings=(None, cache_sh),
            )
            entry = (prefill, decode, (mesh, param_sh))
        self._serve_cache[key] = entry
        return entry

    def generate(
        self,
        prompts,
        gen_len: int,
        max_len: Optional[int] = None,
        *,
        enc_frames=None,
        greedy: bool = True,
        seed: int = 0,
        exact_handoff: bool = False,
    ) -> np.ndarray:
        """Batched generation: bulk prefill → decode loop → (B, gen_len)
        token array.  ``exact_handoff`` forces the token-by-token
        prefill (debug path; also the automatic fallback for recurrent /
        encoder-decoder archs whose states only exist on decode)."""
        prompts = jnp.asarray(prompts, jnp.int32)
        max_len = max_len or int(prompts.shape[1]) + gen_len + 1
        prefill_fn, decode_fn, meshed = self._serve_fns(
            max_len, exact_handoff
        )
        params = self.params
        if meshed is not None:
            from repro.dist.sharding import activation_sharding

            mesh, param_sh = meshed
            # shard the weights once per params version, not per call
            cached = getattr(self, "_serve_params", None)
            if cached is None or cached[0] is not self.params:
                self._serve_params = (
                    self.params, jax.device_put(self.params, param_sh)
                )
            params = self._serve_params[1]
            with mesh, activation_sharding(mesh):
                return serving.generate_tokens(
                    params, self.cfg, prompts, gen_len,
                    prefill_fn=prefill_fn, decode_fn=decode_fn,
                    enc_frames=enc_frames, greedy=greedy, seed=seed,
                )
        return serving.generate_tokens(
            params, self.cfg, prompts, gen_len,
            prefill_fn=prefill_fn, decode_fn=decode_fn,
            enc_frames=enc_frames, greedy=greedy, seed=seed,
        )
