"""train_step / serve_step builders shared by dryrun.py, train.py, serve.py.

The train step includes: microbatched gradient accumulation (lax.scan),
global-norm clipping, cosine LR schedule, the optimizer update, and the
HGC hook — per-example coded weights arrive in ``batch["weights"]`` and
a per-shard-group decode weight ``batch["lam"]`` scales the loss, so the
pjit gradient all-reduce computes the *decoded* coded aggregate
(DESIGN.md §3, integration point 1).
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import tracing
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.models import transformer as tf
from repro.optim import clip_by_global_norm, cosine_schedule, make_optimizer

PyTree = Any

# per-arch optimizer defaults for the production configs: adafactor where
# Adam moments would not fit 16 GB/chip HBM (the 400B MoE).
ARCH_OPTIMIZER = {
    "llama4-maverick-400b-a17b": "adafactor",
    "gemma3-27b": "adafactor",
}


def default_optimizer_name(cfg: ModelConfig, tcfg: TrainConfig) -> str:
    return ARCH_OPTIMIZER.get(cfg.name, tcfg.optimizer)


@tracing.scoped("optimizer")
def _apply_update(optimizer, tcfg: TrainConfig, lr_at, params, opt_state,
                  grads, step):
    """Clip, the optimizer update and its apply: ``(params, opt_state,
    lr, norm of the clipped gradient)``."""
    if tcfg.grad_clip > 0:
        grads = clip_by_global_norm(grads, tcfg.grad_clip)
    lr = lr_at(step)
    updates, new_state = optimizer.update(
        grads, opt_state, params, lr, tcfg.weight_decay
    )
    new_params = jax.tree.map(lambda p, u: p + u, params, updates)
    grad_norm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads))
    )
    return new_params, new_state, lr, grad_norm


def make_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    optimizer=None,
    accum_shardings=None,
) -> Callable:
    """Returns train_step(params, opt_state, batch, step) →
    (params, opt_state, metrics).

    ``accum_shardings``: optional params-shaped NamedSharding tree —
    pins the f32 gradient accumulator to the FSDP param shards so each
    microbatch's gradient reduction lowers as a reduce-scatter instead
    of a full all-reduce (§Perf hillclimb knob).
    """
    if optimizer is None:
        optimizer = make_optimizer(default_optimizer_name(cfg, tcfg))
    lr_at = cosine_schedule(tcfg.lr, tcfg.warmup_steps, tcfg.total_steps)

    def loss_fn(params, batch):
        # HGC hook: batch["weights"] carries coding coefficient × λ_ij
        # per example; the pjit gradient reduction then yields the
        # decoded coded aggregate Σ λ_ij G_ij = g exactly.
        return tf.loss_and_metrics(params, cfg, batch)

    def grads_of(params, batch):
        if tcfg.microbatch and tcfg.microbatch > 0:
            B = batch["tokens"].shape[0]
            mb = min(tcfg.microbatch, B)
            n_micro = max(B // mb, 1)

            # reshape (B, …) → (n_micro, mb, …) and scan over the leading
            # axis: scan's xs slicing keeps the batch-dim sharding intact
            # (a dynamic_slice over a sharded batch dim would force XLA
            # to gather across shards).
            def split(k, x):
                if k == "positions" and x.ndim == 3 and x.shape[1] == B:
                    # M-RoPE positions: (3, B, S) — batch is axis 1
                    r = x.reshape(3, n_micro, mb, x.shape[2])
                    return jnp.moveaxis(r, 1, 0)  # (n_micro, 3, mb, S)
                if x.ndim == 0 or x.shape[0] != B:
                    return None
                return x.reshape(n_micro, mb, *x.shape[1:])

            xs = {k: split(k, v) for k, v in batch.items()}
            consts = {k: v for k, v in batch.items() if xs.get(k) is None}
            xs = {k: v for k, v in xs.items() if v is not None}

            def body(carry, micro_xs):
                acc, msum = carry
                micro = dict(consts)
                micro.update(micro_xs)
                (_, metrics), g = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, micro)
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(a.dtype), acc, g
                )
                return (acc, msum + metrics["loss"]), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            if accum_shardings is not None:
                zeros = jax.tree.map(
                    lambda z, s: jax.lax.with_sharding_constraint(z, s),
                    zeros, accum_shardings,
                )
            (gsum, lsum), _ = lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)), xs
            )
            if "denom" in batch:
                # fixed-denominator (linear/coded) loss: microbatch
                # losses SUM to the full-batch loss — no /n_micro
                grads, metrics = gsum, {"loss": lsum}
            else:
                grads = jax.tree.map(lambda g: g / n_micro, gsum)
                metrics = {"loss": lsum / n_micro}
            return grads, metrics
        (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        return grads, {"loss": metrics["loss"]}

    def train_step(params, opt_state, batch, step):
        grads, metrics = grads_of(params, batch)
        new_params, new_state, lr, grad_norm = _apply_update(
            optimizer, tcfg, lr_at, params, opt_state, grads, step)
        metrics = dict(metrics)
        metrics["lr"] = lr
        metrics["grad_norm"] = grad_norm
        return new_params, new_state, metrics

    train_step.optimizer = optimizer
    return train_step


_WARNED: set = set()


def _warn_once(old: str, new: str) -> None:
    if old in _WARNED:
        return
    _WARNED.add(old)
    warnings.warn(
        f"{old} is deprecated; use {new} instead",
        DeprecationWarning, stacklevel=3,
    )


def make_dist_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    mesh,
    optimizer=None,
    axes: Tuple[str, str] = ("pod", "data"),
) -> Callable:
    """Deprecated direct entry point — :class:`repro.api.CodedSession`
    owns the dist step (mesh, shardings, λ, EF residuals) end to end."""
    _warn_once("steps_lib.make_dist_train_step",
               "repro.api.CodedSession (it compiles and owns the dist "
               "train step)")
    return _make_dist_train_step(cfg, tcfg, mesh, optimizer=optimizer,
                                 axes=axes)


def _make_dist_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    mesh,
    optimizer=None,
    axes: Tuple[str, str] = ("pod", "data"),
) -> Callable:
    """Mesh-aware train step: the coded decode runs as real collectives.

    Returns ``train_step(params, opt_state, batch, lam, residual, step)
    → (params, opt_state, residual, metrics)``.  Each (pod, data) shard
    group receives its own slice of the batch — the examples of worker
    (i, j)'s assigned parts, weighted by the coding coefficients only —
    and computes the gradient of its local weighted loss, which IS its
    encoded message G_ij (eq. 22).  The decode then runs as the
    two-stage λ-weighted psum of :mod:`repro.dist.grad_sync` (eqs.
    25/27); with ``tcfg.grad_compression`` set (int8 | int4 | fp8) the
    cross-pod hop rides the blockwise-quantized + error-feedback path
    of that codec and ``residual``
    threads the per-pod EF state (leaves ``(n_pods, *param_shape)``,
    sharded over "pod" and, under TP, over "model" like the gradient
    leaf it telescopes against; pass an empty dict otherwise).

    A "model" mesh axis of size tp > 1 runs REAL tensor parallelism
    inside the shard_map region: params enter model-sharded per the
    pspec rules of :mod:`repro.dist.sharding` (the same single source
    of truth the pjit path partitions from), the forward runs
    Megatron-style (column-parallel in-projections, row-parallel
    out-projections psum'd over "model", vocab-parallel logits decoded
    by the cross-entropy's single fused psum), and the per-group loss
    comes out replicated across model shards — the loss metric psums
    over "model" exactly once (inside the CE), then only over
    (data, pod).  Because each shard's backward of the replicated
    objective computes ``∂(Σ_shards φ)/∂(local copy)``, gradients are
    corrected before the coded decode: model-sharded leaves divide by
    tp, replicated leaves psum over "model" and divide by tp.

    MoE archs: the λ-weighted decode is exact for the coeff-weighted
    DATA loss only, so λ is folded into the local objective and the
    load-balancing aux gradient is decoded with *uniform* weights
    ``1/(n·m)`` (stragglers included — the aux regularizer must not
    depend on the straggler pattern); the two-stage psum then runs
    unweighted.

    ``tcfg.seq_shard_activations`` turns on sequence parallelism
    through the same ShardCtx seam: between a row-parallel
    reduce-scatter and the next column-parallel all_gather the
    activations (and the remat-saved block outputs) hold only the
    local 1/tp seq block — identical collective bytes, tp× less
    activation state.  The gradient correction then applies against
    :func:`sharding.seq_sharded_mask` (the replicated-leaf psum is
    load-bearing there: per-shard grads are seq-block partials).

    A leading "stage" mesh axis of size pp > 1 additionally runs
    PIPELINE parallelism inside the same shard_map region: the stacked
    layer groups enter stage-sharded on their leading dim (stage s
    holds groups ``[s·G/pp, (s+1)·G/pp)`` — :func:`sharding.
    stage_layer_ranges`), the per-group coded batch splits into
    ``tcfg.microbatches`` microbatches, and a ``lax.scan`` over the
    static schedule table (T = microbatches + pp − 1 ticks; stage s
    works on microbatch t − s at tick t) drives the forward pipeline
    with ``ppermute`` activation handoffs — reverse-mode AD transposes
    the scan + ppermute into the mirrored backward pipeline, so the
    gradient handoffs are the same schedule reversed (GPipe-style
    fill/drain: bubble fraction (pp − 1)/T).  Off-schedule (stage,
    tick) cells compute on garbage-over-zeros that a zero mask keeps
    out of the loss — and, transposed, out of every gradient.  The
    embedding runs on every stage (only stage 0's result enters the
    pipeline); the remainder layers + unembed + CE ride the last
    stage; the whisper encoder runs stage-replicated on the full local
    batch.  Per-stage gradient buckets then flow through the SAME λ
    decode: ``stage_correct`` mirrors ``tp_correct`` over "stage"
    (stage-sharded leaves /pp, stage-replicated leaves psum over
    "stage" — load-bearing: each stage's grads of the embedding/head/
    encoder cover only its own paths — then /pp) before the coded
    psum, and the int8 EF residuals slice stage-wise exactly like the
    gradient leaf they telescope against.

    λ arrives as a runtime (pods, data) operand, so straggler drops and
    elastic replans at fixed (tolerance, K) never recompile — TP, SP
    and PP add only static shape specialization, never λ-dependent
    shapes.  The microbatched accumulation of :func:`make_train_step`
    is not replicated here: the per-group batch is already 1/(n·m) of
    the global batch (the PP microbatches split it further for the
    pipeline, they do not accumulate extra examples).
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist import grad_sync
    from repro.dist import sharding as shard_lib
    from jax import shard_map

    if optimizer is None:
        optimizer = make_optimizer(default_optimizer_name(cfg, tcfg))
    lr_at = cosine_schedule(tcfg.lr, tcfg.warmup_steps, tcfg.total_steps)
    pod_axis, data_axis = axes
    n_pods = mesh.shape[pod_axis]
    n_groups = n_pods * mesh.shape[data_axis]
    compressed = tcfg.grad_compression != "none"
    if compressed:
        from repro.dist import compression as _comp

        if tcfg.grad_compression not in _comp.COMPRESSION_MODES:
            raise ValueError(
                f"grad_compression={tcfg.grad_compression!r} not in "
                f"{('none',) + _comp.COMPRESSION_MODES}"
            )

    ctx = shard_lib.make_shard_ctx(
        mesh, seq_shard=tcfg.seq_shard_activations
    )
    tp = ctx.tp
    if tp > 1:
        shard_lib.validate_tp(cfg, tp)
    pp = ctx.pp
    pp_microbatches = 1
    if pp > 1:
        shard_lib.validate_pp(cfg, pp,
                              microbatches=tcfg.microbatches)
        pp_microbatches = tcfg.microbatches or pp
    # single source of truth: the pjit path's pspec rules, projected
    # onto the model axis for the shard_map region (params enter
    # model-sharded — no replicated entry, no re-shard on exit)
    params_abs, _ = abstract_state(cfg, tcfg, optimizer)
    pspecs = shard_lib.fit_pspecs(
        shard_lib.params_pspecs(params_abs, cfg, mesh, fsdp=tcfg.fsdp,
                                head_aligned=True),
        params_abs, mesh,
    )
    param_specs = shard_lib.model_axis_only(pspecs)
    # SP makes per-shard grads of replicated leaves seq-block partials;
    # the mask tells tp_correct which leaves need the completing psum
    tp_mask = (shard_lib.seq_sharded_mask(pspecs) if ctx.sp
               else shard_lib.model_sharded_mask(pspecs))
    res_spec_tree = jax.tree.map(
        lambda s: P(pod_axis, *tuple(s)), param_specs,
        is_leaf=lambda x: isinstance(x, P),
    )

    def _pipeline_terms(params, batch):
        """Microbatched stage pipeline over this group's coded batch.

        Returns ``(loss_local, aux_tot)``, both replicated across
        stages (via the closing stage psum).  ``loss_local`` matches
        the non-pipelined ``loss_and_metrics`` loss exactly in fp32:
        the per-microbatch nll/weight sums are additive and the
        denominator is shared.  ``aux_tot`` is the per-microbatch MEAN
        of the MoE aux (exactly the full-batch aux at microbatches=1;
        for M > 1 the router capacity and the mean-based balance terms
        see microbatch-sized token counts, the standard pipeline
        semantic).
        """
        M = pp_microbatches
        tokens = batch["tokens"]
        Bl, S = tokens.shape
        if Bl % M:
            raise ValueError(
                f"{cfg.name}: pipeline parallelism needs the per-group "
                f"batch ({Bl} rows) divisible by microbatches={M}"
            )
        mb = Bl // M
        paramsC = tf.cast_params(params, cfg)
        stage = lax.axis_index(shard_lib.STAGE_AXIS)

        def mb_split(k, v):
            if k == "positions" and v.ndim == 3 and v.shape[1] == Bl:
                # M-RoPE positions (3, Bl, S): batch is axis 1
                r = v.reshape(3, M, mb, v.shape[2])
                return jnp.moveaxis(r, 1, 0)  # (M, 3, mb, S)
            if getattr(v, "ndim", 0) == 0 or v.shape[0] != Bl:
                return None
            return v.reshape(M, mb, *v.shape[1:])

        split = {k: mb_split(k, v) for k, v in batch.items()
                 if k != "enc_frames"}
        split = {k: v for k, v in split.items() if v is not None}
        enc_split = enc_pos = None
        if cfg.is_encdec:
            # the encoder runs ONCE, stage-replicated, on the full
            # local batch; each stage's encoder grads cover only its
            # own groups' cross-attention uses and the stage psum of
            # stage_correct completes the layer-wise sum
            enc_out, enc_pos = tf.encode_frames(
                paramsC, cfg, batch["enc_frames"], ctx
            )
            enc_split = enc_out.reshape(M, mb, *enc_out.shape[1:])

        S_loc = S // tp if ctx.sp else S
        T = M + pp - 1
        perm = [(s, s + 1) for s in range(pp - 1)]

        def tick(carry, t):
            x_recv, nll_acc, w_acc, aux_acc = carry
            # stage s works on microbatch t − s; the clip keeps the
            # dynamic slice in-bounds on off-schedule ticks (their
            # output is masked away below)
            cur = jnp.clip(t - stage, 0, M - 1)
            micro = {
                k: lax.dynamic_index_in_dim(v, cur, 0, keepdims=False)
                for k, v in split.items()
            }
            x0, pos = tf.embed_tokens(
                paramsC, cfg, micro["tokens"],
                positions=micro.get("positions"),
                visual_embeds=micro.get("visual_embeds"), ctx=ctx,
            )
            enc_sl = None
            if enc_split is not None:
                enc_sl = lax.dynamic_index_in_dim(
                    enc_split, cur, 0, keepdims=False
                )
            # SPMD uniformity: every stage embeds every tick, but only
            # stage 0's embedding enters the pipeline — elsewhere the
            # ppermute'd carry does (AD routes cotangents accordingly)
            x_in = jnp.where(stage == 0, x0, x_recv)
            x_out, _, aux_g = tf._apply_groups(
                paramsC["groups"], cfg, x_in, pos, enc_sl, enc_pos,
                ctx=ctx,
            )
            nll_sum, w_sum, aux_r = tf.head_loss_terms(
                paramsC, cfg, x_out, micro["targets"],
                micro.get("weights"), pos, enc_sl, enc_pos, ctx=ctx,
            )
            # the static schedule table: cell (stage, tick) is live iff
            # stage ≤ t < stage + M.  Off-schedule cells compute on
            # garbage-over-zeros; the zero mask keeps that out of the
            # loss and (transposed) out of every gradient.
            valid = ((t >= stage) & (t < stage + M)).astype(jnp.float32)
            lastf = jnp.where(stage == pp - 1, valid, 0.0)
            nll_acc = nll_acc + lastf * nll_sum
            w_acc = w_acc + lastf * w_sum
            # per-microbatch-mean aux (== full-batch aux at M == 1)
            aux_acc = aux_acc + (valid * aux_g + lastf * aux_r) / M
            x_send = lax.ppermute(x_out, shard_lib.STAGE_AXIS, perm)
            return (x_send, nll_acc, w_acc, aux_acc), None

        zero = jnp.zeros((), jnp.float32)
        carry0 = (
            jnp.zeros((mb, S_loc, cfg.d_model), jnp.dtype(cfg.dtype)),
            zero, zero, zero,
        )
        (_, nll_acc, w_acc, aux_acc), _ = lax.scan(
            tick, carry0, jnp.arange(T)
        )
        # only the last stage accumulated loss terms; the stage psum
        # both collects them and re-replicates (out_specs leave "stage"
        # unmentioned, which demands replication over it)
        nll_tot = lax.psum(nll_acc, shard_lib.STAGE_AXIS)
        w_tot = lax.psum(w_acc, shard_lib.STAGE_AXIS)
        aux_tot = lax.psum(aux_acc, shard_lib.STAGE_AXIS)
        denom = batch.get("denom")
        if denom is None:
            denom = jnp.maximum(w_tot, 1.0)
        return nll_tot / denom, aux_tot

    def loss_metrics(params, batch):
        """(total, metrics) — the one seam both objectives share."""
        if pp > 1:
            loss_local, aux_tot = _pipeline_terms(params, batch)
            total = loss_local + tf.AUX_WEIGHT * aux_tot
            return total, {"loss": loss_local, "aux_loss": aux_tot}
        return tf.loss_and_metrics(params, cfg, batch, ctx=ctx)

    def loss_fn(params, batch):
        return loss_metrics(params, batch)

    def moe_obj(params, batch, lam_s):
        # λ folded into the data term; aux decoded with uniform weights
        # (a SEPARATE uniform psum in effect: the unweighted two-stage
        # psum below sums λ·∇data + (aw/nm)·∇aux exactly)
        total, m = loss_metrics(params, batch)
        obj = (lam_s.astype(jnp.float32) * m["loss"]
               + (tf.AUX_WEIGHT / n_groups) * m["aux_loss"])
        return obj, m

    def tp_correct(g):
        """Per-shard grads of the model-replicated objective → exact.

        Inside shard_map each shard's backward yields
        ``∂(Σ_shards φ_j)/∂(its copy)``: sharded leaves carry a uniform
        tp factor; replicated leaves additionally hold only their own
        shard's partial paths, so they psum over "model" first.
        """
        if tp == 1:
            return g

        def one(gl, sharded):
            if not sharded:
                gl = lax.psum(gl, shard_lib.MODEL_AXIS)
            return gl / tp

        return jax.tree.map(one, g, tp_mask)

    stage_mask = shard_lib.stage_sharded_mask(pspecs)

    def stage_correct(g):
        """The "stage" twin of :func:`tp_correct`.

        The pipelined objective is replicated across stages (closing
        stage psum), so each stage's backward yields
        ``∂(Σ_stages φ_s)/∂(its copy)``: stage-sharded leaves (the
        layer-group stacks) carry a uniform pp factor; stage-replicated
        leaves (embedding/head/rest/encoder) additionally hold only
        their own stage's paths — stage 0's table grad is the embed
        contribution, the last stage's the unembed one, the encoder's
        per-stage cross-attention uses — so they psum over "stage"
        first (load-bearing, not just a de-duplication).
        """
        if pp == 1:
            return g

        def one(gl, sharded):
            if not sharded:
                gl = lax.psum(gl, shard_lib.STAGE_AXIS)
            return gl / pp

        return jax.tree.map(one, g, stage_mask)

    def local_grads(params, batch, lam, residual):
        lam_s = lam.reshape(())
        if cfg.is_moe:
            (_, m), g = jax.value_and_grad(moe_obj, has_aux=True)(
                params, batch, lam_s
            )
            psum_lam = jnp.ones((), jnp.float32)
        else:
            (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            psum_lam = lam_s
        g = stage_correct(tp_correct(g))
        # decoded loss Σ_ij λ_ij L_ij — matches the single-host weighted
        # loss (weights there carry coeff × λ over the full batch).
        # Under TP the per-group loss is already psum'd over "model"
        # exactly once (inside the CE) ⇒ replicated across model shards;
        # reducing over (data, pod) only avoids double-counting it.
        loss = lax.psum(
            lax.psum(m["loss"] * lam_s.astype(jnp.float32), data_axis),
            pod_axis,
        )
        if compressed:
            g, residual = grad_sync.compressed_coded_psum(
                g, psum_lam, residual, n_pods=n_pods, axes=axes,
                block=tcfg.grad_compression_block,
                mode=tcfg.grad_compression,
            )
        else:
            g = grad_sync.coded_weighted_psum(g, psum_lam, axes)
        if cfg.is_moe:
            aux = lax.psum(
                lax.psum(m["aux_loss"] / n_groups, data_axis), pod_axis
            )
            return g, residual, loss, aux
        return g, residual, loss

    def batch_spec(key, v):
        if getattr(v, "ndim", 0) == 0:
            return P()  # denom: the fixed global normalizer, replicated
        if key == "positions":  # M-RoPE (3, B, S): batch is axis 1
            return P(None, (pod_axis, data_axis), *([None] * (v.ndim - 2)))
        return P((pod_axis, data_axis), *([None] * (v.ndim - 1)))

    def train_step(params, opt_state, batch, lam, residual, step):
        batch_specs = {k: batch_spec(k, v) for k, v in batch.items()}
        res_specs = res_spec_tree if residual else type(residual)()
        out_extra = (P(),) if cfg.is_moe else ()
        fn = shard_map(
            local_grads,
            mesh=mesh,
            in_specs=(param_specs, batch_specs,
                      P(pod_axis, data_axis), res_specs),
            out_specs=(param_specs, res_specs, P()) + out_extra,
            check_vma=False,
        )
        out = fn(params, batch, lam, residual)
        grads, new_residual, loss = out[0], out[1], out[2]
        new_params, new_state, lr, grad_norm = _apply_update(
            optimizer, tcfg, lr_at, params, opt_state, grads, step)
        metrics = {"loss": loss, "lr": lr, "grad_norm": grad_norm}
        if cfg.is_moe:
            metrics["aux_loss"] = out[3]
        return new_params, new_state, new_residual, metrics

    train_step.optimizer = optimizer
    return train_step


def make_serve_step(cfg: ModelConfig) -> Callable:
    """serve_step(params, cache, token) → (logits, new_cache)."""

    def serve_step(params, cache, token):
        return tf.decode_step(params, cfg, token, cache)

    return serve_step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    """prefill_step(params, batch) → (last logits, cache)."""

    def prefill_step(params, batch):
        logits, cache, _ = tf.forward(
            params, cfg, batch["tokens"],
            positions=batch.get("positions"),
            enc_frames=batch.get("enc_frames"),
            return_cache=True,
            last_only=True,
        )
        return logits[:, -1], cache

    return prefill_step


# ----------------------------------------------------------------------
# abstract inputs — the assignment's input_specs()
# ----------------------------------------------------------------------
def input_specs(
    cfg: ModelConfig, shape: ShapeConfig
) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of a cell.

    Weak-type-correct, shardable, no device allocation.  Frontend stubs
    (whisper frames / VLM patch embeds, per the assignment) appear as
    precomputed embedding tensors.
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f32 = jnp.float32
    if shape.kind in ("train", "prefill"):
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
        }
        if shape.kind == "train":
            specs["targets"] = jax.ShapeDtypeStruct((B, S), i32)
            specs["weights"] = jax.ShapeDtypeStruct((B, S), f32)
        if cfg.mrope_sections:
            specs["positions"] = jax.ShapeDtypeStruct((3, B, S), i32)
        if cfg.is_encdec:
            specs["enc_frames"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_len, cfg.d_model), f32
            )
        return specs
    # decode: one new token against a seq_len cache
    return {"token": jax.ShapeDtypeStruct((B, 1), i32)}


def abstract_state(cfg: ModelConfig, tcfg: TrainConfig, optimizer=None):
    """Abstract (params, opt_state) without allocation."""
    if optimizer is None:
        optimizer = make_optimizer(default_optimizer_name(cfg, tcfg))
    params = jax.eval_shape(
        lambda: tf.init_params(jax.random.PRNGKey(0), cfg)
    )
    opt_state = jax.eval_shape(optimizer.init, params)
    return params, opt_state


def abstract_cache(cfg: ModelConfig, shape: ShapeConfig):
    return jax.eval_shape(
        lambda: tf.init_cache(cfg, shape.global_batch, shape.seq_len)
    )
