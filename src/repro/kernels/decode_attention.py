"""Pallas TPU kernel: fused single-token decode attention over the
ring-buffer KV cache.

The serve hot loop (`api/serving.make_decode_fn`) runs one token per
step: q is (B, 1, H, Dh) against a (B, C, Kv, Dh) ring cache whose
write pointer is ``pos % C``.  The XLA path materializes the full
(B, Kv, G, C) score tensor in HBM every token; this kernel keeps the
scores and the online-softmax state (m, l, acc) in VMEM for the whole
cache sweep — per token, HBM sees only q, the cache, and the (B, 1, H,
Dh) output.

Grid: (B·Kv,) — one program per (sequence, kv head); the G query heads
of a GQA group share that program's cache block, so the cache is read
ONCE per group instead of once per query head.  The kv sweep is a
fori_loop over C/bk blocks, mirroring `flash_attention._flash_fwd_kernel`.

Slot validity is derived *inside* the kernel from the ring write
pointer: a slot s of a cache filled to length L = q_pos+1 with
effective window W holds absolute position

    k_pos(s) = s + W · ⌊(L − 1 − s) / W⌋        (W = window or C)

which is negative for never-written slots (mask), and the usual causal
/ sliding-window predicates apply on top.  This reproduces
`models.attention.ring_slot_positions` without materializing the (C,)
position vector in HBM.  q_pos rides in as a (1, 1) i32 operand so the
token index stays a runtime value — the serve loop never recompiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
BK_DECODE = 128
G_PAD = 8  # f32 sublane — query-group rows pad up to this


def _decode_attn_kernel(qpos_ref, q_ref, k_ref, v_ref, o_ref, *,
                        bk: int, cache_size: int, window: int,
                        softcap: float, scale: float):
    # refs: qpos (1, 1) i32; q (1, Gp, Dh); k/v (1, Cp, Dh); o (1, Gp, Dh)
    qp = qpos_ref[0, 0]
    q = q_ref[...][0].astype(jnp.float32) * scale  # (Gp, Dh)
    Gp, Dh = q.shape
    Cp = k_ref.shape[1]
    weff = window if window > 0 else cache_size

    def body(ik, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.dslice(ik * bk, bk), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.dslice(ik * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (Gp, bk)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        slot = ik * bk + jax.lax.broadcasted_iota(
            jnp.int32, (1, bk), 1)
        # ring write pointer → absolute position held by each slot
        k_pos = slot + weff * ((qp + 1 - 1 - slot) // weff)
        ok = (slot < cache_size) & (k_pos >= 0) & (k_pos <= qp)
        if window > 0:
            ok &= qp - k_pos < window
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

    m0 = jnp.full((Gp, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Gp, 1), jnp.float32)
    a0 = jnp.zeros((Gp, Dh), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, Cp // bk, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30))[None].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "bk", "interpret"),
)
def decode_attention_fwd(
    q: jnp.ndarray,        # (B, 1, H, Dh)
    k_cache: jnp.ndarray,  # (B, C, Kv, Dh)
    v_cache: jnp.ndarray,  # (B, C, Kv, Dh)
    q_pos,                 # scalar i32, runtime operand (no recompile)
    window: int = 0,
    softcap: float = 0.0,
    bk: int = BK_DECODE,
    interpret: bool = True,
) -> jnp.ndarray:
    """Fused GQA ring-buffer decode attention; out (B, 1, H, Dh).

    Drop-in for `models.attention.decode_attention` on the self-attn
    ring path: callers pass the raw write-pointer state (q_pos = pos,
    the cfg window, the cache) and the slot-position vector is derived
    in-kernel.  Matches the XLA path to f32 accumulation error
    (tests/test_decode_attention.py).
    """
    B, one, H, Dh = q.shape
    assert one == 1, q.shape
    C, Kv = k_cache.shape[1], k_cache.shape[2]
    G = H // Kv
    assert G * Kv == H, (H, Kv)

    # fold (B, Kv) into the grid; G query heads share one cache block
    qf = q.reshape(B, Kv, G, Dh).reshape(B * Kv, G, Dh)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(B * Kv, C, Dh)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(B * Kv, C, Dh)

    Gp = -(-G // G_PAD) * G_PAD
    Cp = -(-C // bk) * bk
    qf = jnp.pad(qf, ((0, 0), (0, Gp - G), (0, 0)))
    kf = jnp.pad(kf, ((0, 0), (0, Cp - C), (0, 0)))
    vf = jnp.pad(vf, ((0, 0), (0, Cp - C), (0, 0)))
    qpos = jnp.asarray(q_pos, jnp.int32).reshape(1, 1)

    kernel = functools.partial(
        _decode_attn_kernel, bk=bk, cache_size=C, window=window,
        softcap=softcap, scale=1.0 / (Dh ** 0.5),
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * Kv,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b: (0, 0)),
            pl.BlockSpec((1, Gp, Dh), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Cp, Dh), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Cp, Dh), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Gp, Dh), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Kv, Gp, Dh), q.dtype),
        interpret=interpret,
        name="decode_attention_fwd",
    )(qpos, qf, kf, vf)
    return out[:, :G, :].reshape(B, 1, H, Dh)
