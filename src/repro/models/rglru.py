"""RG-LRU recurrent block (RecurrentGemma / Griffin, arXiv:2402.19427).

Gated linear recurrence:
    r_t = σ(y_t W_a + b_a)              (recurrence gate)
    i_t = σ(y_t W_x + b_x)              (input gate)
    a_t = a^{c·r_t},  a = σ(Λ)          (per-channel learned decay, c = 8)
    h_t = a_t ⊙ h_{t−1} + √(1 − a_t²) ⊙ (i_t ⊙ y_t)

Being a first-order linear recurrence, training/prefill uses
``lax.associative_scan`` (log-depth — TPU-friendly; this is the
hardware adaptation of the GPU "linear scan kernel" in the Griffin
paper).  Decode is the O(1) update.

The surrounding block (as in Griffin): two width-``r`` branches — a
GeLU gate branch and a conv1d(4)→RG-LRU branch — merged multiplicatively
then projected back to d_model.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import tracing
from repro.dist.sharding import NULL_CTX

_C = 8.0
_MAX_SQRT_GRAD = 1000.0


def init_rglru_block(rng, d: int, r: int, d_conv: int, dtype) -> Dict:
    ks = jax.random.split(rng, 7)
    s = 0.02
    return {
        "w_gate": (jax.random.normal(ks[0], (d, r)) * s).astype(dtype),
        "w_lin": (jax.random.normal(ks[1], (d, r)) * s).astype(dtype),
        "conv_w": (jax.random.normal(ks[2], (d_conv, r)) * s).astype(dtype),
        "conv_b": jnp.zeros((r,), dtype),
        "w_a": (jax.random.normal(ks[3], (r, r)) * s).astype(dtype),
        "b_a": jnp.zeros((r,), jnp.float32),
        "w_x": (jax.random.normal(ks[4], (r, r)) * s).astype(dtype),
        "b_x": jnp.zeros((r,), jnp.float32),
        # Λ init so that a = σ(Λ) ∈ [0.9, 0.999] as in the paper
        "lam": jnp.log(
            jnp.linspace(0.9, 0.999, r) / (1 - jnp.linspace(0.9, 0.999, r))
        ).astype(jnp.float32),
        "w_out": (jax.random.normal(ks[5], (r, d)) * s).astype(dtype),
    }


def _gates(params, y, ctx=NULL_CTX):
    """Recurrence/input gates on ``y``.

    TP (ctx active): ``y`` carries this shard's block of the recurrence
    width and ``w_a``/``w_x`` are row-parallel — one psum restores the
    full pre-activations, which are then re-sliced to the local block so
    the elementwise recurrence stays shard-local.
    """
    yf = y.astype(jnp.float32)
    r_local = y.shape[-1]
    r_full = params["w_a"].shape[1]
    pre_a = yf @ params["w_a"].astype(jnp.float32)
    pre_x = yf @ params["w_x"].astype(jnp.float32)
    if ctx.active and params["w_a"].shape[0] != r_full:
        # row-parallel gates: one psum for both pre-activation stacks
        pre_a, pre_x = ctx.psum(jnp.stack([pre_a, pre_x]))
    rgate = jax.nn.sigmoid(
        ctx.local_block(pre_a + params["b_a"], r_local)
    )
    igate = jax.nn.sigmoid(
        ctx.local_block(pre_x + params["b_x"], r_local)
    )
    lam = ctx.local_block(params["lam"], r_local)
    log_a = -_C * rgate * jax.nn.softplus(lam)  # log a_t ≤ 0
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2 * log_a), 1e-12))
    b = mult * igate * yf
    return a, b


def rglru_scan(params, y: jnp.ndarray, h0=None,
               ctx=NULL_CTX) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence RG-LRU via associative scan. y: (B, S, r)."""
    a, b = _gates(params, y, ctx)
    if h0 is not None:
        # fold the initial state into the first step
        b = b.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def combine(l, rgt):
        al, bl = l
        ar, br = rgt
        return al * ar, ar * bl + br

    _, h = lax.associative_scan(combine, (a, b), axis=1)
    return h.astype(y.dtype), h[:, -1]


def rglru_step(params, y1: jnp.ndarray, h: jnp.ndarray):
    """Single decode step. y1: (B, 1, r); h: (B, r)."""
    a, b = _gates(params, y1)
    h_new = a[:, 0] * h.astype(jnp.float32) + b[:, 0]
    return h_new.astype(y1.dtype)[:, None, :], h_new


def _causal_conv(seq, w, b):
    K = w.shape[0]
    pad = jnp.pad(seq, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(seq)
    for k in range(K):
        out = out + pad[:, k : k + seq.shape[1], :] * w[k]
    return out + b


@tracing.scoped("rglru")
def rglru_block_forward(params, x: jnp.ndarray, cfg,
                        ctx=NULL_CTX) -> jnp.ndarray:
    """Full recurrent block (train/prefill). x: (B, S, d).

    TP: gate/lin branches are column-parallel over the recurrence
    width, ``w_out`` row-parallel (psum restores the full d output).
    SP (ctx.sp): the linear recurrence is sequential in seq — the
    block gathers the full sequence before the scan (ctx-driven
    fallback, like the SSD block) and the row-parallel ``w_out``
    reduce-scatters back to the local seq block.
    """
    x = ctx.gather_seq(x)  # gather-before-scan: the scan needs all of S
    gate = jax.nn.gelu(x @ params["w_gate"])
    y = x @ params["w_lin"]
    r_local = y.shape[-1]
    y = _causal_conv(y, params["conv_w"],
                     ctx.local_block(params["conv_b"], r_local))
    h, _ = rglru_scan(params, y, ctx=ctx)
    out = (gate * h) @ params["w_out"]
    if ctx.active and params["w_out"].shape[0] != (cfg.lru_width
                                                  or cfg.d_model):
        out = ctx.psum_scatter(out)  # row-parallel out-projection
    else:
        out = ctx.scatter_seq(out)
    return out


def rglru_init_cache(cfg, batch: int, dtype=jnp.float32) -> Dict:
    r = cfg.lru_width or cfg.d_model
    return {
        "h": jnp.zeros((batch, r), dtype),
        "conv": jnp.zeros((batch, cfg.d_conv - 1, r), dtype),
    }


@tracing.scoped("rglru")
def rglru_block_step(params, x1: jnp.ndarray, cache: Dict, cfg):
    """Decode step. x1: (B, 1, d)."""
    gate = jax.nn.gelu(x1 @ params["w_gate"])
    y = x1 @ params["w_lin"]
    hist = jnp.concatenate(
        [cache["conv"], y.astype(cache["conv"].dtype)], axis=1
    )
    K = params["conv_w"].shape[0]
    y = (jnp.einsum("bkc,kc->bc", hist[:, -K:], params["conv_w"])
         + params["conv_b"])[:, None, :]
    hs, h_new = rglru_step(params, y.astype(x1.dtype), cache["h"])
    out = (gate * hs) @ params["w_out"]
    return out, {"h": h_new, "conv": hist[:, 1:]}
