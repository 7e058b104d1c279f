"""Pallas TPU flash-attention (forward) kernel.

Motivation (EXPERIMENTS.md §Perf, gemma3-27b × prefill_32k): ≥50% of
the prefill memory-roofline term is attention score blocks crossing
XLA fusion boundaries.  This kernel keeps the (Bq × Bk) score tile and
the online-softmax state (m, l, acc) in VMEM for the whole kv sweep —
score traffic to HBM drops to ZERO; HBM sees only q, k, v and out.

Grid: (batch·kv_heads·q_groups, S/Bq); the kv loop is a fori_loop
inside the kernel with VMEM accumulators.  Block shapes are MXU-aligned
(Bq=512, Bk=512, Dh multiple of 128 — all assigned configs comply).

Layout: q (BH, S, Dh), k/v (BH, T, Dh) — callers fold (batch, kv_head,
group) into BH (GQA: repeat kv per group or fold groups into BH with a
shared kv index — see ops wrapper).  Causal + sliding-window masks are
iota-derived inside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
BQ = 512
BK = 512


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, bk: int,
                      causal: bool, window: int, scale: float):
    # block refs: q (1, BQ, Dh); k/v (1, T, Dh); o (1, BQ, Dh)
    iq = pl.program_id(1)
    T = k_ref.shape[1]
    Bq = q_ref.shape[1]
    q = q_ref[...][0].astype(jnp.float32) * scale  # (BQ, Dh)
    q_pos = iq * Bq + jax.lax.broadcasted_iota(
        jnp.int32, (Bq, 1), 0)

    def body(ik, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.dslice(ik * bk, bk), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.dslice(ik * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, bk)
        k_pos = ik * bk + jax.lax.broadcasted_iota(
            jnp.int32, (1, bk), 1)
        ok = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window > 0:
            ok &= q_pos - k_pos < window
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    Dh = q_ref.shape[2]
    m0 = jnp.full((Bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Bq, 1), jnp.float32)
    a0 = jnp.zeros((Bq, Dh), jnp.float32)
    n_k = T // bk
    m, l, acc = jax.lax.fori_loop(0, n_k, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30))[None].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"),
)
def flash_attention_fwd(
    q: jnp.ndarray,  # (BH, S, Dh)
    k: jnp.ndarray,  # (BH, T, Dh)
    v: jnp.ndarray,  # (BH, T, Dh)
    causal: bool = True,
    window: int = 0,
    bq: int = BQ,
    bk: int = BK,
    interpret: bool = True,
) -> jnp.ndarray:
    BH, S, Dh = q.shape
    T = k.shape[1]
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    scale = 1.0 / (Dh ** 0.5)
    kernel = functools.partial(
        _flash_fwd_kernel, bk=bk, causal=causal, window=window,
        scale=scale,
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, S // bq),
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, Dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, Dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dh), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dh), q.dtype),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


def flash_attention_gqa(
    q: jnp.ndarray,  # (B, S, H, Dh)
    k: jnp.ndarray,  # (B, T, Kv, Dh)
    v: jnp.ndarray,
    causal: bool = True,
    window: int = 0,
    interpret: bool = True,
) -> jnp.ndarray:
    """GQA wrapper: folds (B, Kv, G) into the kernel's BH axis."""
    B, S, H, Dh = q.shape
    Kv = k.shape[2]
    G = H // Kv
    qf = q.reshape(B, S, Kv, G, Dh).transpose(0, 2, 3, 1, 4)
    qf = qf.reshape(B * Kv * G, S, Dh)
    kf = jnp.repeat(
        k.transpose(0, 2, 1, 3), G, axis=1
    ).reshape(B * Kv * G, -1, Dh)
    vf = jnp.repeat(
        v.transpose(0, 2, 1, 3), G, axis=1
    ).reshape(B * Kv * G, -1, Dh)
    out = flash_attention_fwd(
        qf, kf, vf, causal=causal, window=window, interpret=interpret
    )
    out = out.reshape(B, Kv, G, S, Dh).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, S, H, Dh)
