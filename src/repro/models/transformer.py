"""Pure-JAX model assembly for all assigned architectures.

Design:
  * params are nested dicts of jnp arrays; all weight matrices are 2-D
    (heads fused as H·Dh) so tensor-parallel sharding divides evenly on
    every assigned config,
  * the layer stack is grouped by the config's ``block_pattern`` period
    and scanned with ``lax.scan`` (stacked params ⇒ compact HLO — a 62-
    layer gemma3 lowers as 10 scanned groups of 6 + 2 unrolled layers),
  * ``jax.checkpoint`` (remat) wraps each scanned group,
  * layer kinds: "global" / "local" attention, "ssm" (Mamba-2 SSD),
    "recurrent" (RG-LRU); optional MoE replaces the dense FFN,
  * encoder–decoder (whisper) adds a bidirectional encoder stack and
    cross-attention in every decoder layer,
  * decode paths carry explicit caches (ring buffers for local layers).

Public entry points:
  init_params, forward, loss_and_metrics,
  init_cache, prefill, decode_step
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import tracing
from repro.configs.base import ModelConfig
from repro.dist.sharding import (
    NULL_CTX,
    ShardCtx,
    anchor_activations,
    anchor_embed,
    anchor_logits,
    anchor_replicated,
)
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib

PyTree = Any

#: weight of the MoE load-balancing aux loss in the training objective —
#: the dist train step reuses this to decode the aux gradient with
#: uniform weights (separate psum from the λ-weighted data term)
AUX_WEIGHT = 0.01


# ----------------------------------------------------------------------
# normalization
# ----------------------------------------------------------------------
def _init_norm(cfg: ModelConfig, d: int) -> Dict:
    if cfg.norm == "layer":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32)}


def _norm(p: Dict, x: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if "bias" in p:
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]
    else:
        ms = (xf * xf).mean(-1, keepdims=True)
        out = xf * lax.rsqrt(ms + 1e-6) * p["scale"]
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# layer init
# ----------------------------------------------------------------------
def _init_attn(rng, cfg: ModelConfig, cross: bool = False) -> Dict:
    d, H, Kv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    s = 0.02
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq": (jax.random.normal(ks[0], (d, H * Dh)) * s).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, Kv * Dh)) * s).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, Kv * Dh)) * s).astype(dt),
        "wo": (jax.random.normal(ks[3], (H * Dh, d)) * s).astype(dt),
    }


def _init_mlp(rng, cfg: ModelConfig) -> Dict:
    d, ff = cfg.d_model, (cfg.d_ff_dense or cfg.d_ff)
    dt = jnp.dtype(cfg.param_dtype)
    s = 0.02
    if cfg.mlp == "swiglu":
        k1, k2, k3 = jax.random.split(rng, 3)
        return {
            "wg": (jax.random.normal(k1, (d, ff)) * s).astype(dt),
            "wu": (jax.random.normal(k2, (d, ff)) * s).astype(dt),
            "wd": (jax.random.normal(k3, (ff, d)) * s).astype(dt),
        }
    k1, k2 = jax.random.split(rng, 2)
    return {
        "w1": (jax.random.normal(k1, (d, ff)) * s).astype(dt),
        "w2": (jax.random.normal(k2, (ff, d)) * s).astype(dt),
    }


def _init_layer(rng, cfg: ModelConfig, kind: str, cross: bool = False,
                moe: Optional[bool] = None) -> Dict:
    ks = jax.random.split(rng, 6)
    d = cfg.d_model
    dt = jnp.dtype(cfg.param_dtype)
    if moe is None:
        moe = cfg.is_moe
    p: Dict[str, Any] = {"norm1": _init_norm(cfg, d)}
    if kind in ("global", "local", "enc"):
        p["attn"] = _init_attn(ks[0], cfg)
    elif kind == "ssm":
        p["ssm"] = ssm_lib.init_ssm(
            ks[0], d, cfg.expand, cfg.d_state, cfg.d_conv,
            cfg.ssm_head_dim, dt,
        )
    elif kind == "recurrent":
        p["rglru"] = rglru_lib.init_rglru_block(
            ks[0], d, cfg.lru_width or d, cfg.d_conv, dt
        )
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    if cross:
        p["norm_x"] = _init_norm(cfg, d)
        p["xattn"] = _init_attn(ks[1], cfg)
    if cfg.d_ff > 0 and kind != "ssm":
        p["norm2"] = _init_norm(cfg, d)
        if moe:
            p["moe"] = moe_lib.init_moe(
                ks[2], d, cfg.d_ff, cfg.n_experts, cfg.n_shared_experts, dt
            )
        else:
            p["mlp"] = _init_mlp(ks[2], cfg)
    return p


def init_params(rng, cfg: ModelConfig) -> PyTree:
    ks = jax.random.split(rng, 8)
    d, V = cfg.d_model, cfg.vocab
    dt = jnp.dtype(cfg.param_dtype)
    params: Dict[str, Any] = {
        "embed": {
            "table": (jax.random.normal(ks[0], (V, d)) * 0.02).astype(dt)
        },
        "final_norm": _init_norm(cfg, d),
    }
    if not cfg.tie_embeddings:
        params["head"] = {
            "w": (jax.random.normal(ks[1], (d, V)) * 0.02).astype(dt)
        }
    P = len(cfg.block_pattern)
    n_groups, n_rest = cfg.n_layers // P, cfg.n_layers % P
    cross = cfg.is_encdec

    def stack_layers(rng, count, kind, moe=None):
        # vmap builds each stacked leaf directly; a per-layer list
        # stacked afterwards holds every layer twice, more than one
        # chip has for a 3B-parameter model
        lrngs = jax.random.split(rng, max(count, 1))
        init = functools.partial(_init_layer, cfg=cfg, kind=kind,
                                 cross=cross, moe=moe)
        return jax.vmap(init)(lrngs)

    groups: Dict[str, Any] = {}
    for k in range(P):
        groups[f"p{k}"] = stack_layers(
            jax.random.fold_in(ks[2], k), n_groups, cfg.block_pattern[k],
            moe=cfg.moe_at(k),
        )
    params["groups"] = groups
    rest: Dict[str, Any] = {}
    for k in range(n_rest):
        rest[f"r{k}"] = _init_layer(
            jax.random.fold_in(ks[3], k), cfg, cfg.block_pattern[k], cross,
            moe=cfg.moe_at(k),
        )
    if rest:
        params["rest"] = rest
    if cfg.is_encdec:
        enc: Dict[str, Any] = {
            "enc_norm": _init_norm(cfg, d),
        }
        enc["groups"] = {
            "p0": stack_layers(ks[4], cfg.n_enc_layers, "enc")
        }
        params["encoder"] = enc
    return params


# ----------------------------------------------------------------------
# layer application (full sequence)
# ----------------------------------------------------------------------
def _split_heads(x, n, Dh):
    return x.reshape(*x.shape[:-1], n, Dh)


@tracing.scoped("attention")
def _attn_apply(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig, kind: str,
    positions: jnp.ndarray,
    kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    kv_positions: Optional[jnp.ndarray] = None,
    ctx: ShardCtx = NULL_CTX,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Returns (output, (k, v) for caching). kv_override ⇒ cross-attn.

    TP (ctx active): in-projections are column-parallel (this shard's
    head block — K/V replicate when n_kv_heads doesn't divide tp), the
    out-projection is row-parallel, finished by one psum over "model".
    SP (ctx.sp): ``x`` arrives as the local seq block — attention mixes
    the whole sequence, so the block re-gathers seq up front and the
    row-parallel finish reduce-scatters back to the local block.
    """
    x = ctx.gather_seq(x)
    B, S, d = x.shape
    Dh = cfg.head_dim
    H, Kv = attn_lib.local_head_counts(p, Dh)
    # replicated-KV GQA fallback (TP with n_kv_heads ∤ tp): every shard
    # computes all KV heads but its Q block lives inside ONE KV group
    # (validate_tp guarantees tp % n_kv_heads == 0) — slice that head so
    # the local Q→KV pairing matches the unsharded model.
    kv_slice = (ctx.active and H != cfg.n_heads and Kv == cfg.n_kv_heads
                and Kv > 1)
    q = _split_heads(x @ p["wq"], H, Dh)
    if kv_override is None:
        k = _split_heads(x @ p["wk"], Kv, Dh)
        v = _split_heads(x @ p["wv"], Kv, Dh)
        if kv_slice:
            kv_head = ctx.axis_index() * Kv // ctx.tp
            k = lax.dynamic_slice_in_dim(k, kv_head, 1, axis=2)
            v = lax.dynamic_slice_in_dim(v, kv_head, 1, axis=2)
            Kv = 1
        k_pos_flat = positions[0] if positions.ndim == 3 else positions[0:1]
        if kind != "enc" or cfg.rope_theta > 0:
            q = attn_lib.apply_rope(
                q, positions, cfg.rope_theta, cfg.mrope_sections
            )
            k = attn_lib.apply_rope(
                k, positions, cfg.rope_theta, cfg.mrope_sections
            )
        kv, kvp = (k, v), None
    else:
        k, v = kv_override
        if kv_slice:
            kv_head = ctx.axis_index() * Kv // ctx.tp
            k = lax.dynamic_slice_in_dim(k, kv_head, 1, axis=2)
            v = lax.dynamic_slice_in_dim(v, kv_head, 1, axis=2)
            Kv = 1
        kv, kvp = (k, v), kv_positions
    causal = kind != "enc" and kv_override is None
    window = cfg.window if kind == "local" else 0
    if (cfg.flash and kv_override is None and k.shape[1] == S
            and S % min(cfg.attn_chunk, S) == 0):
        # §Perf: custom-VJP flash attention (self-attention, arange
        # positions) — no (B,H,S,T) residuals saved for backward.
        out = attn_lib.flash_attention(
            q, k, v, causal, window, cfg.logit_softcap,
            cfg.attn_chunk, cfg.q_chunk if S >= 8192 else 0,
        )
    else:
        # flat positions for the chunked path (shared across batch)
        qp = positions[0, 0] if positions.ndim == 3 else positions[0]
        kp = qp if kv_override is None else kv_positions
        out = attn_lib.attention(
            q, k, v, qp, kp,
            causal=causal, window=window, softcap=cfg.logit_softcap,
            kv_chunk=cfg.attn_chunk, q_chunk=cfg.q_chunk,
        )
    out = out.reshape(B, S, H * Dh) @ p["wo"]
    if ctx.active and H != cfg.n_heads:
        out = ctx.psum_scatter(out)  # row-parallel out-projection
    else:
        out = ctx.scatter_seq(out)  # unsharded attn: back to seq block
    return out, kv


@tracing.scoped("mlp")
def _mlp_apply(p: Dict, x: jnp.ndarray, cfg: ModelConfig,
               ctx: ShardCtx = NULL_CTX):
    # SP: the column-parallel up-projections want the full sequence
    # (each shard computes its ff block over every token)
    x = ctx.gather_seq(x)
    if cfg.mlp == "swiglu" and "wg" in p:
        out = (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]
        sharded = p["wd"].shape[0] != (cfg.d_ff_dense or cfg.d_ff)
    else:
        out = jax.nn.gelu(x @ p["w1"]) @ p["w2"]
        sharded = p["w2"].shape[0] != (cfg.d_ff_dense or cfg.d_ff)
    if ctx.active and sharded:
        out = ctx.psum_scatter(out)  # row-parallel down-projection
    else:
        out = ctx.scatter_seq(out)
    return out


def _ckpt_name(x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Tag post-all-reduce block outputs for the remat policy (§Perf)."""
    if cfg.remat_policy == "save_block_outputs":
        from jax.ad_checkpoint import checkpoint_name

        return checkpoint_name(x, "block_out")
    return x


def _remat_wrap(fn, cfg: ModelConfig):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "save_block_outputs":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.save_only_these_names(
                "block_out"),
        )
    return jax.checkpoint(fn)


def _layer_apply(
    p: Dict, x: jnp.ndarray, kind: str, cfg: ModelConfig,
    positions: jnp.ndarray,
    enc_out: Optional[jnp.ndarray] = None,
    enc_positions: Optional[jnp.ndarray] = None,
    ctx: ShardCtx = NULL_CTX,
) -> Tuple[jnp.ndarray, PyTree, jnp.ndarray]:
    """Returns (x_out, cache_entry, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = _norm(p["norm1"], x)
    cache_entry: PyTree = ()
    if kind in ("global", "local", "enc"):
        out, (k, v) = _attn_apply(p["attn"], h, cfg, kind, positions,
                                  ctx=ctx)
        cache_entry = {
            "k": k.reshape(*k.shape[:2], -1),
            "v": v.reshape(*v.shape[:2], -1),
        }
    elif kind == "ssm":
        out = ssm_lib.ssm_forward(p["ssm"], h, cfg, ctx=ctx)
    elif kind == "recurrent":
        out = rglru_lib.rglru_block_forward(p["rglru"], h, cfg, ctx=ctx)
    else:
        raise ValueError(kind)
    x = x + _ckpt_name(out, cfg)
    if "xattn" in p and enc_out is not None:
        h = _norm(p["norm_x"], x)
        kv_loc = attn_lib.local_head_counts(p["xattn"], cfg.head_dim)[1]
        out, _ = _attn_apply(
            p["xattn"], h, cfg, "cross", positions,
            kv_override=(
                _split_heads(enc_out @ p["xattn"]["wk"], kv_loc,
                             cfg.head_dim),
                _split_heads(enc_out @ p["xattn"]["wv"], kv_loc,
                             cfg.head_dim),
            ),
            kv_positions=enc_positions,
            ctx=ctx,
        )
        x = x + out
    if "norm2" in p:
        h = _norm(p["norm2"], x)
        if "moe" in p:
            out, aux = moe_lib.moe_ffn(
                p["moe"], h, cfg.top_k, cfg.capacity_factor,
                ctx=ctx, shared_width=cfg.n_shared_experts * cfg.d_ff,
                n_experts=cfg.n_experts,
            )
        else:
            out = _mlp_apply(p["mlp"], h, cfg, ctx=ctx)
        x = x + _ckpt_name(out, cfg)
    return x, cache_entry, aux


# ----------------------------------------------------------------------
# full forward (train / prefill)
# ----------------------------------------------------------------------
@tracing.scoped("weight_cast")
def cast_params(params: PyTree, cfg: ModelConfig) -> PyTree:
    """The working copy of the weights in the compute dtype.

    Every float32 leaf of two or more dims is cast: the matrices, and
    the per-layer vectors (norm scales, biases, gates) stacked under the
    layer scan.  Only 1-D leaves, such as the final norm's scale, stay
    float32.  A no-op on leaves already in another dtype: weights held
    in the compute dtype (a serve-only session, bf16 master configs).
    """
    tgt = jnp.dtype(cfg.dtype)

    def cast(a):
        if a.ndim >= 2 and a.dtype == jnp.float32 and a.dtype != tgt:
            return a.astype(tgt)
        return a

    return jax.tree.map(cast, params)


@tracing.scoped("embed")
def _embed(params, cfg, tokens, ctx: ShardCtx = NULL_CTX):
    # Gathers from a sharded table hit an SPMD-partitioner verifier bug
    # (invalid dynamic-slice in the "last resort" path).  The table is
    # stored d-sharded; we all-gather a bf16 working copy at the use
    # site — the gather is then trivially partitionable on the batch
    # axis and the all-gather hoists out of the microbatch loop.
    table = params["embed"]["table"].astype(jnp.dtype(cfg.dtype))
    if ctx.active and table.shape[-1] != cfg.d_model:
        # TP: gather the per-shard embedding slices back to full width
        # (the transpose is a reduce-scatter ⇒ exact local table grads)
        return ctx.all_gather(table[tokens], axis=-1)
    x = anchor_replicated(table)[tokens]
    return anchor_embed(x)


def _matmul_f32(x, w, cfg):
    # accumulate the vocab matmul in f32 without materializing f32 weights
    return jax.lax.dot_general(
        x.astype(jnp.dtype(cfg.dtype)), w,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@tracing.scoped("head_loss")
def _unembed(params, cfg, x, ctx: ShardCtx = NULL_CTX):
    x = _norm(params["final_norm"], x)
    # SP: the final norm ran on the local seq block; the vocab-parallel
    # head wants the full sequence back (the CE decode below then still
    # spends exactly ONE psum over "model" — the count is unchanged)
    x = ctx.gather_seq(x)
    if cfg.tie_embeddings:
        w = params["embed"]["table"].T
        if ctx.active and w.shape[0] != cfg.d_model:
            # TP, tied head: the transposed table is row-parallel —
            # slice x to this shard's d-block and psum the partial
            # logits (full-vocab logits, ordinary cross-entropy after)
            return ctx.psum(
                _matmul_f32(ctx.local_block(x, w.shape[0]), w, cfg)
            )
        return _matmul_f32(x, w, cfg)
    # untied head (d, V): column-parallel ⇒ vocab-parallel local logits;
    # the cross-entropy decodes them with one fused psum (see
    # loss_and_metrics)
    return _matmul_f32(x, params["head"]["w"], cfg)


def _run_encoder(params, cfg, frames, ctx: ShardCtx = NULL_CTX):
    """Whisper encoder over precomputed frontend frames (B, T_enc, d)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    pos = jnp.arange(x.shape[1])[None].repeat(x.shape[0], 0)

    def body(x, lp):
        x, _, _ = _layer_apply(lp, x, "enc", cfg, pos, ctx=ctx)
        return x, None

    body = _remat_wrap(body, cfg)
    x, _ = lax.scan(body, x, params["encoder"]["groups"]["p0"])
    return _norm(params["encoder"]["enc_norm"], x)


# ----------------------------------------------------------------------
# forward pieces — shared by the monolithic forward() and the pipelined
# dist train step (launch.steps), which runs them per stage/microbatch
# ----------------------------------------------------------------------
def embed_tokens(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # (B, S)
    positions: Optional[jnp.ndarray] = None,
    visual_embeds: Optional[jnp.ndarray] = None,
    ctx: ShardCtx = NULL_CTX,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Embedding + VLM frontend + default positions + SP seq scatter.

    ``params`` must already be cast (:func:`cast_params`).  Returns
    ``(x, positions)`` with ``x`` in the residual-stream layout the
    block stack consumes (seq-sharded under SP) and ``positions``
    full-length — blocks gather before attending.
    """
    B, S = tokens.shape
    x = _embed(params, cfg, tokens, ctx)
    if visual_embeds is not None:
        # VLM stub: frontend embeddings replace the first n_vis positions
        n_vis = visual_embeds.shape[1]
        x = jnp.concatenate(
            [visual_embeds.astype(x.dtype), x[:, n_vis:]], axis=1
        )
    if positions is None:
        positions = jnp.arange(S)[None].repeat(B, 0)
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(positions, (3, B, S))
    # SP: the residual stream between blocks lives seq-sharded over
    # "model" — slice after the seq-global embedding/frontend work
    x = ctx.scatter_seq(x)
    return x, positions


def encode_frames(
    params: PyTree, cfg: ModelConfig, enc_frames: jnp.ndarray,
    ctx: ShardCtx = NULL_CTX,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Whisper encoder pass → ``(enc_out, enc_positions)``.

    ``params`` must already be cast.  The encoder stays out of the SP
    regime: enc_len need not divide tp and cross-attention consumes the
    full encoder sequence.
    """
    enc_out = _run_encoder(params, cfg, enc_frames, ctx.no_sp())
    return enc_out, jnp.arange(enc_out.shape[1])


def _apply_groups(
    group_params: PyTree,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    enc_out: Optional[jnp.ndarray] = None,
    enc_pos: Optional[jnp.ndarray] = None,
    ctx: ShardCtx = NULL_CTX,
    return_cache: bool = False,
) -> Tuple[jnp.ndarray, Dict, jnp.ndarray]:
    """Scan the stacked layer groups over ``x``.

    ``group_params`` may be the full ``params["groups"]`` stack or a
    stage-local slice of it (pipeline parallelism) — the scan length is
    whatever leading dim the stack carries.  Returns
    ``(x, caches, aux_sum)``.
    """
    period = len(cfg.block_pattern)

    def group_body(x, gp):
        caches = {}
        aux_g = jnp.zeros((), jnp.float32)
        for k in range(period):
            kind = cfg.block_pattern[k]
            x, ce, aux = _layer_apply(
                gp[f"p{k}"], x, kind, cfg, positions,
                enc_out, enc_pos, ctx=ctx,
            )
            x = anchor_activations(x)
            # only the prefill path wants K/V back; the loss path must
            # not stack full-seq cache entries through the scan's ys
            caches[f"p{k}"] = ce if return_cache else ()
            aux_g = aux_g + aux
        return x, (caches, aux_g)

    body = _remat_wrap(group_body, cfg)
    x, (g_caches, g_aux) = lax.scan(body, x, group_params)
    return x, g_caches, g_aux.sum()


def _apply_rest(
    params: PyTree,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    enc_out: Optional[jnp.ndarray] = None,
    enc_pos: Optional[jnp.ndarray] = None,
    ctx: ShardCtx = NULL_CTX,
) -> Tuple[jnp.ndarray, Dict, jnp.ndarray]:
    """The unscanned remainder layers (``n_layers % period``)."""
    rest_caches: Dict = {}
    aux_total = jnp.zeros((), jnp.float32)
    for k in range(cfg.n_layers % len(cfg.block_pattern)):
        kind = cfg.block_pattern[k]
        x, ce, aux = _layer_apply(
            params["rest"][f"r{k}"], x, kind, cfg, positions,
            enc_out, enc_pos, ctx=ctx,
        )
        rest_caches[f"r{k}"] = ce
        aux_total = aux_total + aux
    return x, rest_caches, aux_total


@tracing.scoped("head_loss")
def _ce_nll(
    logits: jnp.ndarray, targets: jnp.ndarray, cfg: ModelConfig,
    ctx: ShardCtx = NULL_CTX,
) -> jnp.ndarray:
    """Per-token negative log-likelihood (B, S).

    TP (ctx active, untied head): logits arrive vocab-parallel and the
    decode spends exactly ONE fused psum over the model axis (logsumexp
    partials + target log-likelihood together).
    """
    V = logits.shape[-1]
    if ctx.active and V != cfg.vocab:
        # vocab-parallel CE: max-shift via pmax (stop_gradient — the
        # shift cancels analytically), then one psum carries both the
        # local exp-sums and this shard's masked target logit
        m = ctx.pmax(lax.stop_gradient(jnp.max(logits, axis=-1)))
        s = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
        v0 = ctx.axis_index() * V
        tloc = targets - v0
        valid = (tloc >= 0) & (tloc < V)
        ll = jnp.take_along_axis(
            logits, jnp.clip(tloc, 0, V - 1)[..., None], axis=-1
        )[..., 0]
        ll = jnp.where(valid, ll, 0.0)
        s, ll = ctx.psum(jnp.stack([s, ll]))
        lse = jnp.log(s) + m
    else:
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, targets[..., None], axis=-1
        )[..., 0]
    return lse - ll


def head_loss_terms(
    params: PyTree,
    cfg: ModelConfig,
    x: jnp.ndarray,
    targets: jnp.ndarray,
    weights: Optional[jnp.ndarray],
    positions: jnp.ndarray,
    enc_out: Optional[jnp.ndarray] = None,
    enc_pos: Optional[jnp.ndarray] = None,
    ctx: ShardCtx = NULL_CTX,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Rest layers + unembed + weighted CE on a block-stack output.

    The pipelined train step runs this on the LAST stage only (masked
    elsewhere); ``params`` must already be cast.  Returns the un-
    normalized terms ``(Σ nll·w, Σ w, aux_rest)`` so the caller picks
    the denominator (the coded paths use the fixed batch "denom").
    """
    x, _, aux = _apply_rest(params, cfg, x, positions, enc_out, enc_pos,
                            ctx=ctx)
    logits = anchor_logits(_unembed(params, cfg, x, ctx))
    nll = _ce_nll(logits, targets, cfg, ctx)
    w = weights if weights is not None else jnp.ones_like(nll)
    return (nll * w).sum(), w.sum(), aux


def forward(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # (B, S)
    positions: Optional[jnp.ndarray] = None,  # (B,S) or (3,B,S)
    enc_frames: Optional[jnp.ndarray] = None,  # (B, T_enc, d) whisper stub
    visual_embeds: Optional[jnp.ndarray] = None,  # (B, n_vis, d) vlm stub
    return_cache: bool = False,
    last_only: bool = False,  # unembed only the final position (prefill)
    ctx: Optional[ShardCtx] = None,  # TP execution seam (dist path)
) -> Any:
    """Full-sequence forward.  Returns logits (B,S,V) [+ cache, aux]."""
    ctx = ctx or NULL_CTX
    params = cast_params(params, cfg)
    enc_out = enc_pos = None
    if cfg.is_encdec:
        if enc_frames is None:
            raise ValueError("encoder-decoder model needs enc_frames")
        enc_out, enc_pos = encode_frames(params, cfg, enc_frames, ctx)
    x, positions = embed_tokens(
        params, cfg, tokens, positions=positions,
        visual_embeds=visual_embeds, ctx=ctx,
    )
    x, g_caches, g_aux = _apply_groups(
        params["groups"], cfg, x, positions, enc_out, enc_pos,
        ctx=ctx, return_cache=return_cache,
    )
    x, rest_caches, rest_aux = _apply_rest(
        params, cfg, x, positions, enc_out, enc_pos, ctx=ctx
    )
    aux_total = g_aux + rest_aux
    if last_only:
        # the final position lives on the last SP shard — re-gather
        # first (serve paths run with ctx inactive; this keeps the SP
        # regime correct for any caller)
        x = ctx.gather_seq(x)[:, -1:]
        ctx = ctx.no_sp()
    logits = anchor_logits(_unembed(params, cfg, x, ctx))
    if return_cache:
        cache = {"groups": g_caches, "rest": rest_caches}
        return logits, cache, aux_total
    return logits, aux_total


def loss_and_metrics(
    params: PyTree,
    cfg: ModelConfig,
    batch: Dict[str, jnp.ndarray],
    aux_weight: float = AUX_WEIGHT,
    ctx: Optional[ShardCtx] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Weighted token cross-entropy.

    ``batch["weights"]`` (B,S) carries padding masks AND the HGC coding
    coefficients (per-example coded weights — see DESIGN.md §3): the
    gradient of this loss IS the worker's encoded message ``G_ij``.

    TP (ctx active, untied head): logits arrive vocab-parallel and the
    cross-entropy decodes them with exactly ONE fused psum over the
    model axis (logsumexp partials + target log-likelihood together) —
    the loss is then replicated across model shards, so the caller's
    pod/data reductions must NOT psum it over "model" again.
    """
    ctx = ctx or NULL_CTX
    logits, aux = forward(
        params, cfg, batch["tokens"],
        positions=batch.get("positions"),
        enc_frames=batch.get("enc_frames"),
        visual_embeds=batch.get("visual_embeds"),
        ctx=ctx,
    )
    nll = _ce_nll(logits, batch["targets"], cfg, ctx)
    w = batch.get("weights")
    if w is None:
        w = jnp.ones_like(nll)
    # "denom": fixed normalizer keeping the loss LINEAR in the weights —
    # required for exact HGC coded aggregation (weights then carry the
    # coding coefficients; the gradient is the coded linear combination).
    denom = batch.get("denom")
    if denom is None:
        denom = jnp.maximum(w.sum(), 1.0)
    loss = (nll * w).sum() / denom
    total = loss + aux_weight * aux
    metrics = {
        "loss": loss,
        "aux_loss": aux,
        "weight_sum": w.sum(),
    }
    return total, metrics


# ----------------------------------------------------------------------
# decode: cache init, prefill, single step
# ----------------------------------------------------------------------
def _cache_len(cfg: ModelConfig, kind: str, max_len: int) -> int:
    if kind == "local" and cfg.window > 0:
        return min(cfg.window, max_len)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype: Optional[str] = None) -> PyTree:
    """Empty decode cache (ring buffers for local layers)."""
    dt = jnp.dtype(dtype or cfg.dtype)
    Kv, Dh = cfg.n_kv_heads, cfg.head_dim
    P = len(cfg.block_pattern)
    n_groups, n_rest = cfg.n_layers // P, cfg.n_layers % P

    def entry(kind, stacked: int = 0):
        if kind in ("global", "local", "enc"):
            C = _cache_len(cfg, kind, max_len)
            shp = (batch, C, Kv * Dh)
            xshp = (batch, cfg.enc_len, Kv * Dh)
            if stacked:
                shp = (stacked,) + shp
                xshp = (stacked,) + xshp
            e = {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt)}
            if cfg.is_encdec:
                e["xk"] = jnp.zeros(xshp, dt)
                e["xv"] = jnp.zeros(xshp, dt)
            return e
        if kind == "ssm":
            c = ssm_lib.ssm_init_cache(cfg, batch)
            if stacked:
                c = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (stacked,) + a.shape), c
                )
            return c
        if kind == "recurrent":
            c = rglru_lib.rglru_init_cache(cfg, batch)
            if stacked:
                c = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (stacked,) + a.shape), c
                )
            return c
        raise ValueError(kind)

    cache = {
        "groups": {
            f"p{k}": entry(cfg.block_pattern[k], n_groups)
            for k in range(P)
        },
        "rest": {
            f"r{k}": entry(cfg.block_pattern[k]) for k in range(n_rest)
        },
        "length": jnp.zeros((), jnp.int32),
    }
    return cache


def fill_cross_cache(params: PyTree, cfg: ModelConfig,
                     enc_frames: jnp.ndarray, cache: PyTree) -> PyTree:
    """Populate per-decoder-layer cross-attention K/V from the encoder.

    Run once before decode for encoder-decoder models (whisper).
    """
    params = cast_params(params, cfg)
    enc_out = _run_encoder(params, cfg, enc_frames)
    P = len(cfg.block_pattern)

    def proj(layer_p):
        return (enc_out @ layer_p["xattn"]["wk"],
                enc_out @ layer_p["xattn"]["wv"])

    cache = jax.tree.map(lambda a: a, cache)  # shallow copy
    for k in range(P):
        gp = params["groups"][f"p{k}"]
        xk, xv = jax.vmap(proj)(gp)  # stacked over groups
        cache["groups"][f"p{k}"]["xk"] = xk.astype(
            cache["groups"][f"p{k}"]["xk"].dtype)
        cache["groups"][f"p{k}"]["xv"] = xv.astype(
            cache["groups"][f"p{k}"]["xv"].dtype)
    for k in range(cfg.n_layers % P):
        rp = params["rest"][f"r{k}"]
        xk, xv = proj(rp)
        cache["rest"][f"r{k}"]["xk"] = xk.astype(
            cache["rest"][f"r{k}"]["xk"].dtype)
        cache["rest"][f"r{k}"]["xv"] = xv.astype(
            cache["rest"][f"r{k}"]["xv"].dtype)
    return cache


@tracing.scoped("attention")
def _decode_self_attn(
    p: Dict, h: jnp.ndarray, kind: str, cfg: ModelConfig,
    cache_entry: PyTree, pos: jnp.ndarray, use_pallas: bool,
) -> Tuple[jnp.ndarray, PyTree]:
    """One token's self-attention against its ring-buffer cache entry;
    returns (output, updated entry)."""
    B = h.shape[0]
    H, Kv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _split_heads(h @ p["wq"], H, Dh)
    k = _split_heads(h @ p["wk"], Kv, Dh)
    v = _split_heads(h @ p["wv"], Kv, Dh)
    posb = jnp.full((B, 1), pos)
    if cfg.mrope_sections:
        posb = jnp.broadcast_to(posb, (3, B, 1))
    q = attn_lib.apply_rope(q, posb, cfg.rope_theta, cfg.mrope_sections)
    k = attn_lib.apply_rope(k, posb, cfg.rope_theta, cfg.mrope_sections)
    C = cache_entry["k"].shape[1]
    window = cfg.window if kind == "local" else 0
    slot = pos % C
    kc = lax.dynamic_update_slice_in_dim(
        cache_entry["k"], k.reshape(B, 1, Kv * Dh).astype(
            cache_entry["k"].dtype), slot, 1)
    vc = lax.dynamic_update_slice_in_dim(
        cache_entry["v"], v.reshape(B, 1, Kv * Dh).astype(
            cache_entry["v"].dtype), slot, 1)
    if use_pallas:
        # fused kernel derives the slot-position vector in VMEM from
        # the ring write pointer (same formula as below)
        from repro.kernels import ops as kernel_ops

        out = kernel_ops.decode_attention(
            q, kc.reshape(B, C, Kv, Dh), vc.reshape(B, C, Kv, Dh),
            pos, window=window, softcap=cfg.logit_softcap,
        )
    else:
        k_pos = attn_lib.ring_slot_positions(
            C, pos + 1, window if window > 0 else C
        )
        out = attn_lib.decode_attention(
            q, kc.reshape(B, C, Kv, Dh), vc.reshape(B, C, Kv, Dh),
            pos, k_pos, window=window, softcap=cfg.logit_softcap,
        )
    out = out.reshape(B, 1, H * Dh) @ p["wo"]
    new_entry = dict(cache_entry)
    new_entry.update({"k": kc, "v": vc})
    return out, new_entry


def _decode_layer(
    p: Dict, x1: jnp.ndarray, kind: str, cfg: ModelConfig,
    cache_entry: PyTree, pos: jnp.ndarray,
    use_pallas: bool = False,
) -> Tuple[jnp.ndarray, PyTree]:
    B = x1.shape[0]
    H, Kv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = _norm(p["norm1"], x1)
    if kind in ("global", "local"):
        out, new_entry = _decode_self_attn(p["attn"], h, kind, cfg,
                                           cache_entry, pos, use_pallas)
    elif kind == "ssm":
        out, new_entry = ssm_lib.ssm_decode_step(p["ssm"], h, cache_entry, cfg)
    elif kind == "recurrent":
        out, new_entry = rglru_lib.rglru_block_step(
            p["rglru"], h, cache_entry, cfg
        )
    else:
        raise ValueError(kind)
    x1 = x1 + out
    if "xattn" in p and isinstance(cache_entry, dict) and "xk" in cache_entry:
        hx = _norm(p["norm_x"], x1)
        with tracing.scope("attention"):
            q = _split_heads(hx @ p["xattn"]["wq"], H, Dh)
            Ce = cache_entry["xk"].shape[1]
            out = attn_lib.decode_attention(
                q,
                cache_entry["xk"].reshape(B, Ce, Kv, Dh),
                cache_entry["xv"].reshape(B, Ce, Kv, Dh),
                jnp.asarray(Ce, jnp.int32),  # attend over the whole encoder
                jnp.arange(Ce),
            )
            out = out.reshape(B, 1, H * Dh) @ p["xattn"]["wo"]
        x1 = x1 + out
    if "norm2" in p:
        h = _norm(p["norm2"], x1)
        if "moe" in p:
            out, _ = moe_lib.moe_ffn(p["moe"], h, cfg.top_k,
                                     cfg.capacity_factor)
        else:
            out = _mlp_apply(p["mlp"], h, cfg)
        x1 = x1 + out
    return x1, new_entry


def decode_step(
    params: PyTree,
    cfg: ModelConfig,
    token: jnp.ndarray,  # (B, 1) int32
    cache: PyTree,
    use_pallas: Optional[bool] = None,
) -> Tuple[jnp.ndarray, PyTree]:
    """One decode step against the cache; returns (logits (B,V), cache).

    ``use_pallas=None`` auto-selects the fused ring-buffer decode-
    attention kernel on TPU (``kernels.decode_attention``) and the XLA
    path elsewhere; True forces the kernel (interpret mode off-TPU —
    the parity configuration tests/test_decode_attention.py pins).
    Only the self-attention ring path switches; ssm / recurrent /
    cross-attention layers are unaffected.
    """
    if use_pallas is None:
        from repro.kernels.ops import on_tpu

        use_pallas = on_tpu()
    pos = cache["length"]
    params = cast_params(params, cfg)
    x = _embed(params, cfg, token)
    P = len(cfg.block_pattern)

    def group_body(x, scanned):
        group_params, group_cache = scanned
        new_cache = {}
        for k in range(P):
            kind = cfg.block_pattern[k]
            x, ne = _decode_layer(
                group_params[f"p{k}"], x, kind, cfg,
                group_cache[f"p{k}"], pos, use_pallas=use_pallas,
            )
            new_cache[f"p{k}"] = ne
        return x, new_cache

    x, new_g_cache = lax.scan(
        group_body, x, (params["groups"], cache["groups"])
    )
    new_rest = {}
    for k in range(cfg.n_layers % P):
        kind = cfg.block_pattern[k]
        x, ne = _decode_layer(
            params["rest"][f"r{k}"], x, kind, cfg,
            cache["rest"][f"r{k}"], pos, use_pallas=use_pallas,
        )
        new_rest[f"r{k}"] = ne
    logits = anchor_logits(_unembed(params, cfg, x)[:, 0])
    new_cache = dict(cache)
    new_cache.update(
        {"groups": new_g_cache, "rest": new_rest, "length": pos + 1}
    )
    return logits, new_cache


def prefill(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    enc_frames: Optional[jnp.ndarray] = None,
    visual_embeds: Optional[jnp.ndarray] = None,
    last_only: bool = False,
) -> Tuple[jnp.ndarray, PyTree]:
    """Full-sequence forward that also materializes the decode cache.

    Note: for "local" layers the produced cache is the *full-length*
    K/V (the ring-buffer view is only used in decode_step); prefill→
    decode handoff trims to the window (:func:`prefill_to_decode_cache`).
    ``last_only`` unembeds only the final position — the serving path
    never needs the full (B, S, V) logits.
    """
    logits, cache, _ = forward(
        params, cfg, tokens, enc_frames=enc_frames,
        visual_embeds=visual_embeds, return_cache=True,
        last_only=last_only,
    )
    return logits, cache


def bulk_prefill_supported(cfg: ModelConfig) -> bool:
    """Whether the bulk prefill → decode-cache handoff covers this arch.

    The full-sequence forward only materializes attention K/V cache
    entries; recurrent states (SSD, RG-LRU) and the encoder-decoder
    cross caches exist only on the decode path, so those archs hand off
    token-by-token (the exact-handoff fallback).
    """
    return (set(cfg.block_pattern) <= {"global", "local"}
            and not cfg.is_encdec)


def prefill_to_decode_cache(
    cfg: ModelConfig,
    prefill_cache: PyTree,
    max_len: int,
    dtype: Optional[str] = None,
) -> PyTree:
    """Re-lay a bulk-prefill cache into ``decode_step``'s layout.

    Prefill K/V entries are full-length ``(…, S, Kv·Dh)``; the decode
    cache holds ``(…, C, Kv·Dh)`` ring buffers with ``C =
    min(window, max_len)`` for local layers (``max_len`` for global)
    and slot convention ``slot = pos % C`` — so the handoff keeps the
    last ``min(S, C)`` positions and scatters each to its ring slot,
    reproducing exactly the state ``S`` decode steps would have built.
    """
    if not bulk_prefill_supported(cfg):
        raise ValueError(
            f"{cfg.name}: bulk prefill handoff needs an attention-only "
            f"decoder (pattern {cfg.block_pattern}); use the exact "
            f"token-by-token handoff"
        )
    dt = jnp.dtype(dtype or cfg.dtype)
    P = len(cfg.block_pattern)
    S = None

    def convert(entry, kind):
        nonlocal S
        C = _cache_len(cfg, kind, max_len)
        k, v = entry["k"], entry["v"]
        S = k.shape[-2]
        if kind != "local" and S > C:
            raise ValueError(
                f"prompt length {S} exceeds cache size {C} — raise "
                f"max_len"
            )
        keep = min(S, C)
        pos = jnp.arange(S - keep, S)
        slots = pos % C  # distinct (a contiguous run of length ≤ C)

        def scatter(x):
            buf = jnp.zeros(x.shape[:-2] + (C, x.shape[-1]), dt)
            return buf.at[..., slots, :].set(
                x[..., S - keep :, :].astype(dt)
            )

        return {"k": scatter(k), "v": scatter(v)}

    cache = {
        "groups": {
            f"p{k}": convert(prefill_cache["groups"][f"p{k}"],
                             cfg.block_pattern[k])
            for k in range(P)
        },
        "rest": {
            f"r{k}": convert(prefill_cache["rest"][f"r{k}"],
                             cfg.block_pattern[k])
            for k in range(cfg.n_layers % P)
        },
    }
    cache["length"] = jnp.asarray(S, jnp.int32)
    return cache
