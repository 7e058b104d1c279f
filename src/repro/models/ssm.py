"""Mamba-2 (SSD — state-space duality) block, chunked matmul form.

Faithful to the SSD algorithm of arXiv:2405.21060 (minimal form):
per-head scalar decay  dA_t = exp(dt_t · A),  inputs discretized as
x̄_t = dt_t · x_t, state  H_t = dA_t·H_{t−1} + x̄_t ⊗ B_t,
output y_t = C_t · H_t + D · x_t.

The chunked form splits the sequence into chunks of Q tokens:
  * intra-chunk:  Y_in = ((C Bᵀ) ⊙ L) x̄   (quadratic within the chunk —
    MXU-friendly matmuls; L is the decay lower-triangle),
  * inter-chunk:  per-chunk states are propagated by a short lax.scan.

Decode is the O(1) recurrent update on a carried (B, nh, hd, N) state.
TPU adaptation note: chunk size is chosen so the intra-chunk matrices
(Q×Q and hd×N) are multiples of the MXU tile; no custom kernel needed —
the SSD form is already matmul-dominant, which is the paper's own point.

Tensor parallelism (dist path, ``ShardCtx`` active): the projections are
head-block structured — ``zproj``/``xproj``/``dtproj`` (and the xs
depthwise conv) are column-parallel over whole SSD heads, B/C streams
(``bcproj`` + their conv) replicate (they are shared across heads in the
minimal SSD form), per-head vectors (A_log, D, dt_bias) are sliced to
the local head block, and ``out_proj`` is row-parallel with one psum.
This per-segment split is exactly why the in-projection is separate
leaves instead of one fused matrix: a blockwise shard of the fused
``in_proj`` would cut across the z/x/B/C/dt segment boundaries.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import tracing
from repro.dist.sharding import NULL_CTX


def init_ssm(rng, d: int, expand: int, d_state: int, d_conv: int,
             head_dim: int, dtype) -> Dict:
    di = expand * d
    nh = di // head_dim
    ks = jax.random.split(rng, 7)
    scale = 0.02
    return {
        # column-parallel, head-block structured (see module docstring)
        "zproj": (jax.random.normal(ks[0], (d, di)) * scale).astype(dtype),
        "xproj": (jax.random.normal(ks[1], (d, di)) * scale).astype(dtype),
        # B/C streams: shared across heads ⇒ replicated under TP
        "bcproj": (jax.random.normal(ks[2], (d, 2 * d_state)) * scale
                   ).astype(dtype),
        "dtproj": (jax.random.normal(ks[3], (d, nh)) * scale).astype(dtype),
        "conv_x_w": (jax.random.normal(ks[4], (d_conv, di)) * scale
                     ).astype(dtype),
        "conv_x_b": jnp.zeros((di,), dtype),
        "conv_bc_w": (jax.random.normal(ks[5], (d_conv, 2 * d_state))
                      * scale).astype(dtype),
        "conv_bc_b": jnp.zeros((2 * d_state,), dtype),
        "A_log": jnp.log(
            jnp.linspace(1.0, 16.0, nh).astype(jnp.float32)
        ),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "out_proj": (jax.random.normal(ks[6], (di, d)) * scale).astype(dtype),
    }


def _causal_conv(seq: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray):
    """Depthwise causal conv along time: seq (B,S,C), w (K,C)."""
    K = w.shape[0]
    pad = jnp.pad(seq, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(seq)
    for k in range(K):  # K=4: unrolled adds, fuses well
        out = out + pad[:, k : k + seq.shape[1], :] * w[k]
    return jax.nn.silu(out + b)


def _segsum(logdA: jnp.ndarray) -> jnp.ndarray:
    """L[i,j] = exp(Σ_{k=j+1..i} logdA_k) for j ≤ i else 0. (..., Q, Q)."""
    Q = logdA.shape[-1]
    cs = jnp.cumsum(logdA, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # Σ_{j+1..i}
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    # mask BEFORE the exp: above the diagonal diff is a positive sum
    # that overflows to inf at long chunks, and where(mask, inf, 0)
    # has a NaN gradient (0 · inf)
    return jnp.exp(jnp.where(mask, diff, -jnp.inf))


@tracing.scoped("ssd")
def ssd_chunked(
    xbar: jnp.ndarray,  # (B, S, nh, hd)  = dt · x
    logdA: jnp.ndarray,  # (B, S, nh)      = dt · A  (A < 0)
    Bc: jnp.ndarray,  # (B, S, N)
    Cc: jnp.ndarray,  # (B, S, N)
    chunk: int,
    h0: jnp.ndarray = None,  # (B, nh, hd, N) initial state
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan; returns (y (B,S,nh,hd), final state)."""
    B, S, nh, hd = xbar.shape
    N = Bc.shape[-1]
    assert S % chunk == 0, (S, chunk)
    c = S // chunk
    xb = xbar.reshape(B, c, chunk, nh, hd).astype(jnp.float32)
    la = logdA.reshape(B, c, chunk, nh).astype(jnp.float32)
    Bb = Bc.reshape(B, c, chunk, N).astype(jnp.float32)
    Cb = Cc.reshape(B, c, chunk, N).astype(jnp.float32)

    # intra-chunk (dual / attention-like form)
    L = _segsum(jnp.moveaxis(la, -1, -2))  # (B, c, nh, Q, Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cb, Bb)  # (B,c,Q,Q)
    M = scores[:, :, None] * L  # (B,c,nh,Q,Q)
    y_in = jnp.einsum("bchqk,bckhd->bcqhd", M, xb)

    # per-chunk summarized state:  S_c = Σ_j decay_to_end_j · x̄_j ⊗ B_j
    cs = jnp.cumsum(la, axis=2)  # (B,c,Q,nh)
    decay_end = jnp.exp(cs[:, :, -1:, :] - cs)  # decay from j to chunk end
    S_c = jnp.einsum(
        "bcqh,bcqhd,bcqn->bchdn", decay_end, xb, Bb
    )  # (B,c,nh,hd,N)
    chunk_decay = jnp.exp(cs[:, :, -1, :])  # (B,c,nh) total chunk decay

    # inter-chunk recurrence over c chunks
    if h0 is None:
        h0 = jnp.zeros((B, nh, hd, N), jnp.float32)

    def body(h, inputs):
        s_c, dec = inputs  # (B,nh,hd,N), (B,nh)
        h_new = h * dec[..., None, None] + s_c
        return h_new, h  # emit state *entering* the chunk

    (h_final, h_enter) = lax.scan(
        body,
        h0,
        (jnp.moveaxis(S_c, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_enter = jnp.moveaxis(h_enter, 0, 1)  # (B,c,nh,hd,N)

    # contribution of the entering state within each chunk
    decay_in = jnp.exp(cs)  # decay from chunk start to position q
    y_out = jnp.einsum(
        "bcqn,bchdn,bcqh->bcqhd", Cb, h_enter, decay_in
    )
    y = (y_in + y_out).reshape(B, S, nh, hd)
    return y, h_final


def ssd_reference(xbar, logdA, Bc, Cc, h0=None):
    """Naive per-token recurrence — oracle for the chunked form."""
    B, S, nh, hd = xbar.shape
    N = Bc.shape[-1]
    h = (jnp.zeros((B, nh, hd, N), jnp.float32) if h0 is None
         else h0.astype(jnp.float32))
    ys = []
    for t in range(S):
        dA = jnp.exp(logdA[:, t].astype(jnp.float32))  # (B,nh)
        h = h * dA[..., None, None] + jnp.einsum(
            "bhd,bn->bhdn", xbar[:, t].astype(jnp.float32),
            Bc[:, t].astype(jnp.float32),
        )
        ys.append(jnp.einsum("bhdn,bn->bhd", h, Cc[:, t].astype(jnp.float32)))
    return jnp.stack(ys, axis=1), h


def _head_params(params: Dict, nh_local: int, ctx):
    """Per-head vectors sliced to this shard's head block (TP no-op
    when the projections are unsharded)."""
    A_log = ctx.local_block(params["A_log"], nh_local)
    D = ctx.local_block(params["D"], nh_local)
    dt_bias = ctx.local_block(params["dt_bias"], nh_local)
    return A_log, D, dt_bias


@tracing.scoped("ssm")
def ssm_forward(
    params: Dict,
    x: jnp.ndarray,  # (B, S, d)
    cfg,
    ctx=NULL_CTX,
) -> jnp.ndarray:
    """Full-sequence Mamba-2 block (train / prefill).

    SP (ctx.sp): the SSD recurrence is sequential in seq, so the block
    cannot keep the sequence sharded through the scan — it gathers the
    full sequence up front (the ctx-driven fallback) and the
    row-parallel out-projection reduce-scatters back to the local seq
    block; only the norm/residual work *between* blocks shards.
    """
    x = ctx.gather_seq(x)  # gather-before-scan: the scan needs all of S
    hd = cfg.ssm_head_dim
    z = x @ params["zproj"]      # (B, S, di_local)
    xs = x @ params["xproj"]     # (B, S, di_local)
    bc = x @ params["bcproj"]    # (B, S, 2N) replicated under TP
    dt = x @ params["dtproj"]    # (B, S, nh_local)
    di_l = xs.shape[-1]
    nh_l = di_l // hd
    xs = _causal_conv(
        xs, params["conv_x_w"],
        ctx.local_block(params["conv_x_b"], di_l),
    )
    bc = _causal_conv(bc, params["conv_bc_w"], params["conv_bc_b"])
    Bc, Cc = jnp.split(bc, 2, axis=-1)
    A_log, D, dt_bias = _head_params(params, nh_l, ctx)
    xh = xs.reshape(*xs.shape[:2], nh_l, hd)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
    A = -jnp.exp(A_log)
    xbar = xh.astype(jnp.float32) * dt[..., None]
    logdA = dt * A
    y, _ = ssd_chunked(xbar, logdA, Bc, Cc,
                       chunk=min(cfg.ssm_chunk, x.shape[1]))
    y = y + D[None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(*x.shape[:2], di_l).astype(x.dtype)
    y = y * jax.nn.silu(z)  # gated
    out = y @ params["out_proj"]
    if ctx.active and params["out_proj"].shape[0] != cfg.expand * cfg.d_model:
        out = ctx.psum_scatter(out)  # row-parallel out-projection
    else:
        out = ctx.scatter_seq(out)  # unsharded heads: back to seq block
    return out


def ssm_init_cache(cfg, batch: int, dtype=jnp.float32) -> Dict:
    di = cfg.expand * cfg.d_model
    nh = di // cfg.ssm_head_dim
    conv_dim = di + 2 * cfg.d_state
    return {
        "h": jnp.zeros((batch, nh, cfg.ssm_head_dim, cfg.d_state), dtype),
        "conv": jnp.zeros((batch, cfg.d_conv - 1, conv_dim), dtype),
    }


@tracing.scoped("ssm")
def ssm_decode_step(
    params: Dict,
    x: jnp.ndarray,  # (B, 1, d)
    cache: Dict,
    cfg,
) -> Tuple[jnp.ndarray, Dict]:
    di = cfg.expand * cfg.d_model
    hd = cfg.ssm_head_dim
    nh = di // hd
    z = x @ params["zproj"]
    xs = x @ params["xproj"]
    bc = x @ params["bcproj"]
    dt = x @ params["dtproj"]
    conv_in = jnp.concatenate([xs, bc], axis=-1)  # (B,1,di+2N)
    hist = jnp.concatenate(
        [cache["conv"], conv_in.astype(cache["conv"].dtype)], axis=1
    )
    w = jnp.concatenate([params["conv_x_w"], params["conv_bc_w"]], axis=-1)
    b = jnp.concatenate([params["conv_x_b"], params["conv_bc_b"]], axis=-1)
    K = w.shape[0]
    conv_out = jax.nn.silu(
        jnp.einsum("bkc,kc->bc", hist[:, -K:], w) + b
    )[:, None, :]
    xs, Bc, Cc = jnp.split(conv_out, [di, di + cfg.d_state], axis=-1)
    xh = xs.reshape(xs.shape[0], nh, hd).astype(jnp.float32)
    dt1 = jax.nn.softplus(
        dt[:, 0].astype(jnp.float32) + params["dt_bias"]
    )  # (B, nh)
    A = -jnp.exp(params["A_log"])
    dA = jnp.exp(dt1 * A)  # (B, nh)
    h = cache["h"] * dA[..., None, None] + jnp.einsum(
        "bhd,bn->bhdn", xh * dt1[..., None], Bc[:, 0].astype(jnp.float32)
    )
    y = jnp.einsum("bhdn,bn->bhd", h, Cc[:, 0].astype(jnp.float32))
    y = y + params["D"][None, :, None] * xh
    y = y.reshape(x.shape[0], 1, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    out = y @ params["out_proj"]
    new_cache = {"h": h, "conv": hist[:, 1:]}
    return out, new_cache
