"""Pallas TPU kernel: coded gradient combine  out = C @ G.

C (R, K) is the coding matrix (R worker rows or one decode row), G
(K, F) the stacked per-part flattened gradients — F is the model size
(10⁶–10¹¹), K ≤ a few hundred.  This is the encode (eq. 22) / decode
(eqs. 25/27) hot-spot of the paper.

TPU adaptation (DESIGN.md §3): a GPU implementation would stripe K over
thread blocks; on TPU we keep the skinny K axis resident in VMEM and
tile the huge F axis so each grid step is one MXU-shaped (Rb×K)·(K×Fb)
matmul:

  grid  = (R/Rb, F/Fb)
  C blk = (Rb, K)     — revisited per F tile (tiny, stays in VMEM)
  G blk = (K, Fb)     — streamed from HBM
  out   = (Rb, Fb)

Fb = 512 keeps the working set (K·Fb + Rb·K + Rb·Fb) ≪ 16 MB VMEM for
K ≤ 2048 and is lane-aligned (128); Rb = 8 matches the f32 sublane.
The quantized variants (``coded_combine_q`` / ``_q4`` / ``_f8``) share
one dequant-combine kernel with a lane-dense row layout (see below).
Every kernel is validated in interpret mode on CPU (tests/test_kernels.py)
and compiled for a described v5e chip (tests/test_tpu_compile.py) via
the same pallas_call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

R_BLOCK = 8
F_BLOCK = 512


def _combine_kernel(c_ref, g_ref, o_ref):
    # c_ref: (Rb, K), g_ref: (K, Fb), o_ref: (Rb, Fb)
    c = c_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.dot(
        c, g, preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def coded_combine(
    coeff: jnp.ndarray,  # (R, K)
    grads: jnp.ndarray,  # (K, F)
    interpret: bool = True,
) -> jnp.ndarray:
    """out (R, F) = coeff @ grads, tiled for VMEM.  Pads R and F."""
    R, K = coeff.shape
    K2, F = grads.shape
    assert K == K2, (coeff.shape, grads.shape)
    Rp = -(-R // R_BLOCK) * R_BLOCK
    Fp = -(-F // F_BLOCK) * F_BLOCK
    cp = jnp.pad(coeff, ((0, Rp - R), (0, 0)))
    gp = jnp.pad(grads, ((0, 0), (0, Fp - F)))
    out = pl.pallas_call(
        _combine_kernel,
        grid=(Rp // R_BLOCK, Fp // F_BLOCK),
        in_specs=[
            pl.BlockSpec((R_BLOCK, K), lambda r, f: (r, 0)),
            pl.BlockSpec((K, F_BLOCK), lambda r, f: (0, f)),
        ],
        out_specs=pl.BlockSpec((R_BLOCK, F_BLOCK), lambda r, f: (r, f)),
        out_shape=jax.ShapeDtypeStruct((Rp, Fp), grads.dtype),
        interpret=interpret,
        name="coded_combine",
    )(cp, gp)
    return out[:R, :F]


# ----------------------------------------------------------------------
# fused dequant combine (compressed cross-pod hop)
# ----------------------------------------------------------------------
# Both operands keep a lane-dense layout reached by free reshapes: the
# payload (K, P) as (K, rows, 128) and the scales (K, nb) as
# (K, nb / 128, 128).  With ``per`` payload lanes per scale block
# (block, or block / 2 for packed int4), one grid step takes
# SCALE_ROWS scale rows = SCALE_ROWS·per payload rows, so every block
# shape meets the TPU (8, 128) tiling rule.  Each scale row expands to
# its (per, 128) payload rows with in-vreg lane gathers.  Coefficients
# ride SMEM as scalars; the combine is a VPU multiply-accumulate into
# the f32 output block (the hop's R is 1 and K the pod count, far too
# skinny for the MXU).
LANES = 128
SCALE_ROWS = 8  # f32 sublane tile


def _gather_lanes(x, idx):
    return jnp.take_along_axis(x, idx, axis=1, mode="promise_in_bounds")


def _expand_scales(srows, per: int):
    """(Q, 128) scale rows → (Q·per, 128): payload lane (r, j) gets the
    scale of block (r·128 + j) // per."""
    u = jax.lax.broadcasted_iota(jnp.int32, (per, LANES), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (per, LANES), 1)
    idx = (u * LANES + j) // per
    return jnp.concatenate(
        [_gather_lanes(jnp.broadcast_to(srows[q:q + 1], (per, LANES)), idx)
         for q in range(srows.shape[0])],
        axis=0,
    )


def _interleave(lo, hi):
    """(TR, 128) even / odd values → (TR, 256) in value order."""
    j = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 1)
    even = j % 2 == 0
    tiles = []
    for half in range(2):
        idx = j // 2 + (LANES // 2) * half
        tiles.append(jnp.where(even, _gather_lanes(lo, idx),
                               _gather_lanes(hi, idx)))
    return jnp.concatenate(tiles, axis=1)


def _dequant_combine_kernel(c_ref, g_ref, s_ref, o_ref, *, per: int,
                            int4: bool):
    # c: SMEM (R, K); g: (K, TR, 128) payload; s: (K, SCALE_ROWS, 128);
    # o: (R, TR, 128 or 256 for int4) f32
    R, K = c_ref.shape
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def over_k(k, carry):
        s = _expand_scales(s_ref[k], per)
        if int4:
            # byte j holds values 2j (low nibble) and 2j+1 (high), both
            # in the same scale block
            p = g_ref[k].astype(jnp.int32) & 0xFF
            lo = (((p & 0xF) ^ 8) - 8).astype(jnp.float32) * s
            hi = ((((p >> 4) & 0xF) ^ 8) - 8).astype(jnp.float32) * s
            deq = _interleave(lo, hi)
        else:
            deq = g_ref[k].astype(jnp.float32) * s

        def over_r(r, c2):
            o_ref[r] += c_ref[r, k] * deq
            return c2

        return jax.lax.fori_loop(0, R, over_r, carry)

    jax.lax.fori_loop(0, K, over_k, 0)


def _dequant_combine(coeff, grads_q, scales, block: int, interpret: bool,
                     int4: bool):
    R, K = coeff.shape
    K2, P = grads_q.shape
    vals = 2 if int4 else 1  # values per payload element
    per = block // vals
    if K != K2 or (P * vals) % block or block % vals:
        raise ValueError(
            f"coeff {coeff.shape} / payload {grads_q.shape} do not fit "
            f"block={block}"
        )
    if per % 8 or (LANES % per and per % LANES):
        raise ValueError(
            f"block={block}: {per} payload lanes per scale must be a "
            f"multiple of 8 that divides {LANES} or is a multiple of it"
        )
    F = P * vals
    TR = SCALE_ROWS * per
    rows = -(-P // (TR * LANES)) * TR
    Pp = rows * LANES
    # zero pads (payload and scales) contribute exactly 0
    gp = jnp.pad(grads_q, ((0, 0), (0, Pp - P)))
    sp = jnp.pad(scales.astype(jnp.float32),
                 ((0, 0), (0, Pp // per - scales.shape[1])))
    out = pl.pallas_call(
        functools.partial(_dequant_combine_kernel, per=per, int4=int4),
        grid=(rows // TR,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((K, TR, LANES), lambda t: (0, t, 0)),
            pl.BlockSpec((K, SCALE_ROWS, LANES), lambda t: (0, t, 0)),
        ],
        out_specs=pl.BlockSpec((R, TR, LANES * vals), lambda t: (0, t, 0)),
        out_shape=jax.ShapeDtypeStruct((R, rows, LANES * vals),
                                       jnp.float32),
        interpret=interpret,
        name="dequant_combine_" + ("int4" if int4
                                   else jnp.dtype(grads_q.dtype).name),
    )(coeff.astype(jnp.float32), gp.reshape(K, rows, LANES),
      sp.reshape(K, -1, LANES))
    return out.reshape(R, Pp * vals)[:, :F]


@functools.partial(
    jax.jit, static_argnames=("block", "interpret")
)
def coded_combine_q(
    coeff: jnp.ndarray,  # (R, K) f32
    grads_q: jnp.ndarray,  # (K, F) int8
    scales: jnp.ndarray,  # (K, F // block) f32
    block: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    """Fused int8-dequant coded combine (compression path).

    The de-quantization happens in VMEM right before the combine — HBM
    only ever sees int8 gradients (4× traffic cut vs f32).
    """
    return _dequant_combine(coeff, grads_q, scales, block, interpret,
                            int4=False)


@functools.partial(
    jax.jit, static_argnames=("block", "interpret")
)
def coded_combine_q4(
    coeff: jnp.ndarray,  # (R, K) f32
    grads_q: jnp.ndarray,  # (K, F // 2) int8, two int4 values per byte
    scales: jnp.ndarray,  # (K, F // block) f32
    block: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    """Fused packed-int4 dequant coded combine.

    ``grads_q`` carries two nibbles per byte in
    :func:`repro.dist.compression.pack_int4` layout (value 2i in the
    low nibble of byte i) — 8× less HBM/wire traffic than f32.  The
    sign-extend + scale + interleave all happen in VMEM.
    """
    return _dequant_combine(coeff, grads_q, scales, block, interpret,
                            int4=True)


@functools.partial(
    jax.jit, static_argnames=("block", "interpret")
)
def coded_combine_f8(
    coeff: jnp.ndarray,  # (R, K) f32
    grads_q: jnp.ndarray,  # (K, F) float8_e4m3fn
    scales: jnp.ndarray,  # (K, F // block) f32
    block: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    """Fused fp8-e4m3 dequant coded combine.

    Identical tiling to :func:`coded_combine_q`, but the payload is a
    blockwise-scaled float8 — same 4× traffic cut as int8 with relative
    (rather than fixed-grid) per-value precision.  The f32 upcast
    happens in VMEM.
    """
    return _dequant_combine(coeff, grads_q, scales, block, interpret,
                            int4=False)
