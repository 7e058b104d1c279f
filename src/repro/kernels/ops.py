"""Public jit'd wrappers over the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode; on TPU
the same pallas_call compiles to Mosaic.  ``encode_tree`` /
``decode_tree`` wire the kernel into the HGC pytree world.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.coded_combine import (
    coded_combine,
    coded_combine_f8,
    coded_combine_q,
    coded_combine_q4,
)
from repro.kernels.decode_attention import decode_attention_fwd

PyTree = Any


def on_tpu() -> bool:
    """True iff the default jax backend is a real TPU.

    The one place the ``use_pallas`` defaults come from (kernels run
    compiled on TPU, interpret-mode elsewhere).
    """
    return jax.default_backend() == "tpu"


def combine(coeff, grads, use_pallas: bool = True):
    """out = coeff @ grads with the kernel (interpret on CPU)."""
    if not use_pallas:
        return ref.coded_combine_ref(coeff, grads)
    return coded_combine(coeff, grads, interpret=not on_tpu())


def combine_q(coeff, grads_q, scales, block: int = 128,
              use_pallas: bool = True):
    if not use_pallas:
        return ref.coded_combine_q_ref(coeff, grads_q, scales, block)
    return coded_combine_q(
        coeff, grads_q, scales, block=block, interpret=not on_tpu()
    )


def combine_q4(coeff, grads_q, scales, block: int = 128,
               use_pallas: bool = True):
    """Packed-int4 fused dequant combine (grads_q is (K, F//2) bytes)."""
    if not use_pallas:
        return ref.coded_combine_q4_ref(coeff, grads_q, scales, block)
    return coded_combine_q4(
        coeff, grads_q, scales, block=block, interpret=not on_tpu()
    )


def combine_f8(coeff, grads_q, scales, block: int = 128,
               use_pallas: bool = True):
    """fp8-e4m3 fused dequant combine."""
    if not use_pallas:
        return ref.coded_combine_f8_ref(coeff, grads_q, scales, block)
    return coded_combine_f8(
        coeff, grads_q, scales, block=block, interpret=not on_tpu()
    )


#: compression mode → fused dequant-combine wrapper
COMBINE_BY_MODE = {
    "int8": combine_q,
    "int4": combine_q4,
    "fp8": combine_f8,
}


def combine_compressed(mode: str, coeff, grads_q, scales,
                       block: int = 128, use_pallas: bool = True):
    """Dispatch the fused combine matching a compression codec."""
    try:
        fn = COMBINE_BY_MODE[mode]
    except KeyError:
        raise ValueError(
            f"no fused combine for compression mode {mode!r}"
        ) from None
    return fn(coeff, grads_q, scales, block=block, use_pallas=use_pallas)


def decode_attention(q, k_cache, v_cache, q_pos, window: int = 0,
                     softcap: float = 0.0, use_pallas: bool = True):
    """Ring-buffer GQA decode attention; out (B, 1, H, Dh).

    With ``use_pallas=False`` the jnp oracle runs (slot positions
    materialized via the same ring formula the kernel derives in VMEM).
    """
    if not use_pallas:
        C = k_cache.shape[1]
        weff = window if window > 0 else C
        s = jnp.arange(C)
        k_pos = s + weff * ((q_pos - s) // weff)
        return ref.decode_attention_ref(
            q, k_cache, v_cache, q_pos, k_pos,
            window=window, softcap=softcap,
        )
    return decode_attention_fwd(
        q, k_cache, v_cache, q_pos, window=window, softcap=softcap,
        interpret=not on_tpu(),
    )


def flatten_tree(tree: PyTree) -> jnp.ndarray:
    return jnp.concatenate([jnp.ravel(x) for x in jax.tree.leaves(tree)])


def unflatten_like(vec: jnp.ndarray, tree: PyTree) -> PyTree:
    leaves = jax.tree.leaves(tree)
    treedef = jax.tree.structure(tree)
    out = []
    off = 0
    for l in leaves:
        n = int(np.prod(l.shape)) if l.shape else 1
        out.append(vec[off : off + n].reshape(l.shape).astype(l.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


def encode_messages(code, g_parts: jnp.ndarray) -> jnp.ndarray:
    """All workers' messages G_ij at once: (Σm_i, F) = E @ g_parts.

    ``E`` is the collapsed encoding matrix (worker coeffs ⊙ layer-1
    rows) — one kernel launch instead of Σm_i separate combines.
    """
    E = jnp.asarray(code.encoding_matrix_flat(), jnp.float32)
    return combine(E, g_parts)


def decode_gradient(code, messages: jnp.ndarray, fast_edges,
                    fast_workers) -> jnp.ndarray:
    """Decoded full gradient from worker messages via λ weights."""
    lam = jnp.asarray(
        code.collapsed_weights(fast_edges, fast_workers), jnp.float32
    )
    return combine(lam[None, :], messages)[0]
