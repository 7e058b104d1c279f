"""Blockwise gradient compression for the edge→master hop.

The paper's runtime model (§IV-A) makes the edge↔master link the scarce
resource (τ_e up to 10× τ_w); quantizing the per-edge partial aggregate
``G_i`` (eq. 25) cuts that hop's bytes while the in-pod worker↔edge
stage stays exact.  Three codecs share one contract — flat payload
padded to a block multiple, one f32 scale per block, exact-zero pad
region — so the fused Pallas dequant-combine kernels
(:mod:`repro.kernels.coded_combine`) consume any of them:

  ========  ======================  ==================  ==============
  mode      payload                 bytes per value     scale formula
  ========  ======================  ==================  ==============
  int8      int8, one per value     1                   max|x| / 127
  int4      two nibbles per int8    0.5 (packed)        max|x| / 7
  fp8       float8_e4m3fn           1                   max|x| / 448
  ========  ======================  ==================  ==============

Error feedback (:func:`compress_error_feedback`) keeps the *time-
averaged* transmitted gradient unbiased for every codec, which is what
SGD needs when the same hop is compressed every iteration.

Pad invariant: the flat vector is zero-padded up to a block multiple,
and the pad positions are masked OUT of the per-block scale reduction —
pad values can never influence a block's scale, and they quantize to
exactly 0 in every codec (asserted by tests/test_kernels.py), so the
kernel-side combine over the padded tail contributes nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

PyTree = Any

DEFAULT_BLOCK = 256

#: symmetric quantization range per codec (max representable magnitude)
_QMAX = {"int8": 127.0, "int4": 7.0, "fp8": 448.0}

COMPRESSION_MODES = tuple(_QMAX)


@dataclasses.dataclass(frozen=True)
class QuantMeta:
    """Static shape info needed to undo a blockwise quantizer."""

    shape: Tuple[int, ...]
    block: int
    pad: int
    mode: str = "int8"


def _blocked(x, block: int):
    """Flatten + zero-pad to a block multiple; per-block scales with the
    pad positions masked out of the max reduction (the pad invariant)."""
    x = jnp.asarray(x, jnp.float32)
    shape = tuple(x.shape)
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    n = flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    mags = jnp.abs(blocks)
    if pad:
        valid = (jnp.arange(flat.size) < n).reshape(-1, block)
        mags = jnp.where(valid, mags, 0.0)
    amax = jnp.max(mags, axis=1)
    return blocks, amax, shape, pad


def quantize_int8(x, block: int = DEFAULT_BLOCK):
    """Blockwise symmetric int8: returns ``(q, scales, meta)``.

    ``q`` is a flat int8 vector (zero-padded to a block multiple so it
    feeds ``coded_combine_q`` directly), ``scales`` one f32 per block
    (max-abs / 127).  Max elementwise error ≤ max|x| / 127 · (1/2 + ε).
    """
    blocks, amax, shape, pad = _blocked(x, block)
    scales = amax / 127.0
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(
        jnp.round(blocks / safe[:, None]), -127, 127
    ).astype(jnp.int8)
    return q.reshape(-1), scales, QuantMeta(
        shape=shape, block=block, pad=pad, mode="int8")


def dequantize_int8(q, scales, meta: QuantMeta):
    """Inverse of :func:`quantize_int8` (up to rounding error)."""
    blocks = jnp.asarray(q).reshape(-1, meta.block).astype(jnp.float32)
    flat = (blocks * jnp.asarray(scales)[:, None]).reshape(-1)
    n = flat.size - meta.pad
    return flat[:n].reshape(meta.shape)


# ----------------------------------------------------------------------
# int4: two nibbles per int8 byte
# ----------------------------------------------------------------------
def pack_int4(vals: jnp.ndarray) -> jnp.ndarray:
    """Pack an even-length int vector in [-8, 7] into nibbles.

    Element 2i rides the LOW nibble of byte i, element 2i+1 the HIGH
    nibble (the layout ``coded_combine_q4`` unpacks in VMEM).
    """
    v = jnp.asarray(vals, jnp.int32) & 0xF
    lo = v[0::2]
    hi = v[1::2]
    return (lo | (hi << 4)).astype(jnp.uint8).view(jnp.int8)


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_int4` → int32 values in [-8, 7]."""
    p = jnp.asarray(packed).view(jnp.uint8).astype(jnp.int32)
    lo = ((p & 0xF) ^ 8) - 8          # sign-extend the low nibble
    hi = (((p >> 4) & 0xF) ^ 8) - 8   # sign-extend the high nibble
    return jnp.stack([lo, hi], axis=-1).reshape(-1)


def quantize_int4(x, block: int = DEFAULT_BLOCK):
    """Blockwise symmetric packed int4: ``(q_packed, scales, meta)``.

    ``q_packed`` is int8 of HALF the padded length — two values per
    byte — for a 8× byte cut vs f32 on the wire.  Values are clipped to
    [-7, 7] (scale = max-abs / 7) so the code stays symmetric.  ``block``
    must be even (nibble pairs never straddle a scale block).
    """
    if block % 2:
        raise ValueError(f"int4 needs an even block, got {block}")
    blocks, amax, shape, pad = _blocked(x, block)
    scales = amax / 7.0
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(jnp.round(blocks / safe[:, None]), -7, 7).astype(
        jnp.int32)
    packed = pack_int4(q.reshape(-1))
    return packed, scales, QuantMeta(
        shape=shape, block=block, pad=pad, mode="int4")


def dequantize_int4(q_packed, scales, meta: QuantMeta):
    """Inverse of :func:`quantize_int4` (up to rounding error)."""
    vals = unpack_int4(q_packed).astype(jnp.float32)
    blocks = vals.reshape(-1, meta.block)
    flat = (blocks * jnp.asarray(scales)[:, None]).reshape(-1)
    n = flat.size - meta.pad
    return flat[:n].reshape(meta.shape)


# ----------------------------------------------------------------------
# fp8 (e4m3): blockwise-scaled float payload
# ----------------------------------------------------------------------
def quantize_fp8(x, block: int = DEFAULT_BLOCK):
    """Blockwise-scaled fp8-e4m3: ``(q_f8, scales, meta)``.

    The block scale maps max|x| onto the e4m3 max normal (448), so the
    payload spends its exponent range on the block's dynamic range —
    relative error ~2^-3 per value vs int8's fixed 1/127 absolute grid.
    """
    blocks, amax, shape, pad = _blocked(x, block)
    scales = amax / 448.0
    safe = jnp.where(scales > 0, scales, 1.0)
    q = (blocks / safe[:, None]).astype(jnp.float8_e4m3fn)
    return q.reshape(-1), scales, QuantMeta(
        shape=shape, block=block, pad=pad, mode="fp8")


def dequantize_fp8(q, scales, meta: QuantMeta):
    """Inverse of :func:`quantize_fp8` (up to e4m3 rounding error)."""
    blocks = jnp.asarray(q).astype(jnp.float32).reshape(-1, meta.block)
    flat = (blocks * jnp.asarray(scales)[:, None]).reshape(-1)
    n = flat.size - meta.pad
    return flat[:n].reshape(meta.shape)


# ----------------------------------------------------------------------
# mode dispatch (the one seam grad_sync / trees go through)
# ----------------------------------------------------------------------
_QUANTIZE = {
    "int8": quantize_int8,
    "int4": quantize_int4,
    "fp8": quantize_fp8,
}
_DEQUANTIZE = {
    "int8": dequantize_int8,
    "int4": dequantize_int4,
    "fp8": dequantize_fp8,
}


def quantize(x, block: int = DEFAULT_BLOCK, mode: str = "int8"):
    """Blockwise quantize under any codec: ``(payload, scales, meta)``."""
    try:
        return _QUANTIZE[mode](x, block=block)
    except KeyError:
        raise ValueError(
            f"unknown compression mode {mode!r} "
            f"(choose from {COMPRESSION_MODES})"
        ) from None


def dequantize(q, scales, meta: QuantMeta):
    """Inverse of :func:`quantize` — the codec rides ``meta.mode``."""
    return _DEQUANTIZE[meta.mode](q, scales, meta)


def wire_bytes_per_value(mode: str, block: int = DEFAULT_BLOCK) -> float:
    """Cross-pod bytes per gradient value (payload + amortized scales)."""
    payload = {"int8": 1.0, "int4": 0.5, "fp8": 1.0}[mode]
    return payload + 4.0 / block


# ----------------------------------------------------------------------
# pytree wrappers
# ----------------------------------------------------------------------
def _is_qleaf(x) -> bool:
    return isinstance(x, dict) and set(x) == {"q", "scales", "meta"}


def quantize_tree(tree: PyTree, block: int = DEFAULT_BLOCK,
                  mode: str = "int8") -> PyTree:
    """Quantize every leaf; result mirrors the tree with q-leaf dicts."""

    def one(x):
        q, s, meta = quantize(x, block=block, mode=mode)
        return {"q": q, "scales": s, "meta": meta}

    return jax.tree.map(one, tree)


def dequantize_tree(qtree: PyTree) -> PyTree:
    """Inverse of :func:`quantize_tree`."""
    return jax.tree.map(
        lambda d: dequantize(d["q"], d["scales"], d["meta"]),
        qtree,
        is_leaf=_is_qleaf,
    )


def init_pod_residuals(tree: PyTree, n_pods: int) -> PyTree:
    """Zero EF residuals for the sharded train step, one row per pod.

    Leaves are ``(n_pods, *leaf.shape)`` f32 — sharded ``P("pod")`` they
    hand each pod its own residual inside the shard_map region (see
    :func:`repro.dist.grad_sync.compressed_coded_psum`).  The layout is
    codec-independent: int8/int4/fp8 all carry f32 residuals, so a
    checkpointed residual restores under any ``grad_compression``.
    """
    return jax.tree.map(
        lambda x: jnp.zeros((n_pods,) + tuple(x.shape), jnp.float32), tree
    )


def compress_error_feedback(
    tree: PyTree, residual: PyTree, block: int = DEFAULT_BLOCK,
    mode: str = "int8",
) -> Tuple[PyTree, PyTree]:
    """One EF-SGD compression round: ``(q_tree, new_residual)``.

    Quantizes ``tree + residual``; the new residual is what the
    low-precision payload failed to carry, so transmitted values
    telescope — the sum of T dequantized sends equals ``T·tree`` up to
    one residual.  The telescoping identity holds for every codec
    because the residual is always computed against the local dequant
    of the exact payload the wire carries.
    """
    target = jax.tree.map(lambda g, r: g + r, tree, residual)
    qtree = quantize_tree(target, block=block, mode=mode)
    sent = dequantize_tree(qtree)
    new_residual = jax.tree.map(lambda t, s: t - s, target, sent)
    return qtree, new_residual
