"""Two-stage coded gradient aggregation as shard_map collectives.

The distributed form of the paper's decode pipeline on a
(pod × data × model) mesh, where pod=edge and data=worker:

  worker encode (eq. 22)  G_ij = Σ_k d^i_jk b_ik g_k   — the weighted
      loss of ``launch.steps`` already yields G_ij as the local gradient;
  edge decode (eq. 25)    G_i  = Σ_{j∈F_i} c^i_j G_ij  — ``psum`` over
      the "data" axis;
  master decode (eq. 27)  g    = Σ_{i∈F} a_i G_i       — ``psum`` over
      the "pod" axis.

Because λ_ij = a_i·c^i_j enters as a *runtime scalar operand*
(:func:`lam_array_from_code`), a straggler drop changes only an input
array — the compiled step is reused, zero recompilation (the headline
elasticity claim).  The bandwidth-limited edge→master hop optionally
rides :mod:`repro.dist.compression`; host-side bulk encode/decode rides
the Pallas ``coded_combine`` kernel via :mod:`repro.kernels.ops`.
"""
from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import tracing
from repro.dist import compression
from repro.kernels import ops as kernel_ops

PyTree = Any

WORKER_AXIS = "data"  # within-edge aggregation axis (eq. 25)
EDGE_AXIS = "pod"     # cross-edge aggregation axis (eq. 27)


# ----------------------------------------------------------------------
# λ weights: the dist ↔ core seam
# ----------------------------------------------------------------------
def lam_array_from_code(
    code,
    fast_edges: Sequence[int],
    fast_workers: Sequence[Sequence[int]],
    pods: int,
    data: int,
    dtype=np.float32,
) -> np.ndarray:
    """Collapsed per-worker decode weights λ_ij as a (pods, data) array.

    Row i is edge/pod i, column j worker/data-group j; equals
    ``HGCCode.collapsed_weights`` reshaped onto the mesh (stragglers 0).
    """
    if (code.topo.n, code.topo.m) != (pods, (data,) * pods):
        raise ValueError(
            f"code topology {code.topo.m} does not match the "
            f"({pods}×{data}) mesh"
        )
    lam = code.collapsed_weights(fast_edges, fast_workers)
    return np.asarray(lam, dtype).reshape(pods, data)


# ----------------------------------------------------------------------
# in-shard_map collective (call from inside a shard_map region)
# ----------------------------------------------------------------------
@tracing.scoped("lambda_decode")
def coded_weighted_psum(
    tree: PyTree,
    lam,
    axes: Tuple[str, str] = (EDGE_AXIS, WORKER_AXIS),
) -> PyTree:
    """λ-weighted hierarchical psum of this shard group's gradient.

    ``lam`` is THIS group's scalar λ_ij.  Stage 1 sums λ-weighted
    messages over the worker axis (edge decode, eq. 25); stage 2 sums
    the per-edge partials over the pod axis (master decode, eq. 27).
    Stragglers participate with λ=0 — shapes never change.
    """
    pod_axis, worker_axis = axes
    lam = jnp.asarray(lam)

    def one(x):
        y = x * lam.astype(x.dtype)
        y = lax.psum(y, worker_axis)  # workers → edge   (eq. 25)
        y = lax.psum(y, pod_axis)     # edges   → master (eq. 27)
        return y

    return jax.tree.map(one, tree)


@tracing.scoped("codec")
def compressed_coded_psum(
    tree: PyTree,
    lam,
    residual: PyTree,
    *,
    n_pods: int,
    axes: Tuple[str, str] = (EDGE_AXIS, WORKER_AXIS),
    block: int = 64,
    mode: str = "int8",
    use_pallas=None,
) -> Tuple[PyTree, PyTree]:
    """λ-weighted decode with a quantized + error-feedback cross-pod hop.

    In-shard_map counterpart of :func:`coded_weighted_psum` for the
    bandwidth-limited regime: stage 1 (worker→edge, eq. 25) stays an
    exact psum; the per-edge partial plus this pod's EF residual is then
    blockwise quantized (``mode`` ∈ int8 | int4 | fp8, see
    :mod:`repro.dist.compression`), all-gathered across the pod axis
    and combined through the matching fused dequant kernel (eq. 27 over
    quantized payloads — 4× fewer cross-pod bytes for int8/fp8, 8× for
    packed int4).  ``residual`` leaves carry a leading per-pod axis
    (local block size 1 inside shard_map) and stay f32 for every codec,
    so checkpoints restore under any ``mode``; the returned residual is
    what the low-precision payload failed to carry, so transmitted
    values telescope (EF-SGD — time-averaged gradient stays unbiased).

    Returns ``(decoded_tree, new_residual)``.
    """
    pod_axis, worker_axis = axes
    if use_pallas is None:
        use_pallas = kernel_ops.on_tpu()
    lam = jnp.asarray(lam)

    def leaf(x, r):
        y = x * lam.astype(jnp.float32)
        y = lax.psum(y, worker_axis)  # exact edge decode (eq. 25)
        target = y + r.reshape(y.shape).astype(jnp.float32)
        q, scales, meta = compression.quantize(target, block=block,
                                               mode=mode)
        # local dequant: the EF update needs what the wire will carry
        sent = compression.dequantize(q, scales, meta)
        new_r = (target - sent).reshape(r.shape).astype(r.dtype)
        qs = lax.all_gather(q, pod_axis)       # (n_pods, payload)
        ss = lax.all_gather(scales, pod_axis)  # (n_pods, nb)
        ones = jnp.ones((1, n_pods), jnp.float32)
        out = kernel_ops.combine_compressed(
            mode, ones, qs, ss, block=block, use_pallas=use_pallas
        )[0]
        return out[: y.size].reshape(y.shape).astype(x.dtype), new_r

    flat_x, treedef = jax.tree.flatten(tree)
    flat_r = jax.tree.leaves(residual)
    if len(flat_x) != len(flat_r):
        raise ValueError(
            f"residual has {len(flat_r)} leaves, gradients {len(flat_x)}"
        )
    outs = [leaf(x, r) for x, r in zip(flat_x, flat_r)]
    decoded = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_residual = jax.tree.unflatten(
        jax.tree.structure(residual), [o[1] for o in outs]
    )
    return decoded, new_residual


# ----------------------------------------------------------------------
# mesh-level builders (wrap shard_map; jit-compatible)
# ----------------------------------------------------------------------
def make_coded_allreduce(mesh, axes: Tuple[str, str] = (EDGE_AXIS, WORKER_AXIS)):
    """``runner(tree, lam)``: the two-stage decode as a mesh program.

    ``lam``: (pods, data) array of λ_ij (zeros drop stragglers).  The
    tree is a REPLICATED value standing in for every group's local
    contribution — shard_map hands each (pod, data) group the same
    leaves, weights them by that group's λ_ij and runs the two psum
    stages, so the result is Σ_ij λ_ij · tree (used to validate the
    hierarchical reduction against a flat sum).  For *distinct*
    per-group gradients, call :func:`coded_weighted_psum` from inside
    the train step's own shard_map region, where each group's gradient
    is already device-local (see tests/test_dist_core_seam.py).
    """
    pod_axis, worker_axis = axes

    def inner(tree, lam_block):
        return coded_weighted_psum(tree, lam_block.reshape(()), axes)

    fn = shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(), P(pod_axis, worker_axis)),
        out_specs=P(),
        check_vma=False,
    )

    def runner(tree: PyTree, lam) -> PyTree:
        return fn(tree, jnp.asarray(lam, jnp.float32))

    return runner


def make_compressed_cross_pod_sum(
    mesh,
    axes: Tuple[str, str] = (EDGE_AXIS, WORKER_AXIS),
    block: int = 64,
    mode: str = "int8",
):
    """Coded all-reduce with a quantized edge→master hop.

    Stage 1 (worker→edge, in-pod links) stays exact; the per-edge
    partial is then blockwise quantized before crossing the pod
    boundary — the bytes that actually traverse the scarce edge↔master
    link shrink 4× (int8/fp8) or 8× (packed int4).  All pods' payloads
    + scales are gathered and combined with unit coefficients through
    the matching fused dequant-matmul Pallas kernel
    (``coded_combine_q`` / ``_q4`` / ``_f8``), mirroring the TPU hot
    path.
    """
    pod_axis, worker_axis = axes
    n_pods = mesh.shape[pod_axis]
    use_pallas = kernel_ops.on_tpu()

    def inner(tree, lam_block):
        lam = lam_block.reshape(())

        def leaf(x):
            y = x * lam.astype(jnp.float32)
            y = lax.psum(y, worker_axis)  # exact edge decode (eq. 25)
            q, scales, _ = compression.quantize(y, block=block,
                                                mode=mode)
            # gather every edge's partial payload + scales at the master
            qs = lax.all_gather(q, pod_axis)       # (n, payload)
            ss = lax.all_gather(scales, pod_axis)  # (n, nb)
            ones = jnp.ones((1, n_pods), jnp.float32)
            out = kernel_ops.combine_compressed(
                mode, ones, qs, ss, block=block, use_pallas=use_pallas
            )[0]
            return out[: y.size].reshape(y.shape)

        return jax.tree.map(leaf, tree)

    fn = shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(), P(pod_axis, worker_axis)),
        out_specs=P(),
        check_vma=False,
    )

    def runner(tree: PyTree, lam) -> PyTree:
        return fn(tree, jnp.asarray(lam, jnp.float32))

    return runner


# ----------------------------------------------------------------------
# host-side bulk encode/decode (Pallas coded_combine hot path)
# ----------------------------------------------------------------------
def encode_messages(code, g_parts) -> jnp.ndarray:
    """All workers' encoded messages (Σm_i, F) in one kernel launch."""
    return kernel_ops.encode_messages(code, g_parts)


def decode_gradient(code, messages, fast_edges, fast_workers) -> jnp.ndarray:
    """Decoded full gradient from worker messages via the λ weights."""
    return kernel_ops.decode_gradient(code, messages, fast_edges, fast_workers)
