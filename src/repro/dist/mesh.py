"""Test meshes for host-device shard_map runs.

The production meshes live in ``repro.launch.mesh`` (256/512 chips);
this factory builds the small (pod × data × model) meshes used by the
multi-device CPU tests (``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

HGC mapping: "pod" = edge layer, "data" = worker layer within an edge,
"model" = tensor-parallel shards of one worker group, "stage" = pipeline
stages (each stage replicates the coded (pod, data) farm for its own
contiguous layer block).
"""
from __future__ import annotations

import jax


def make_test_mesh(pods: int, data: int, model: int, stages: int = 1):
    """(stage × pods × data × model) mesh with the canonical axis names.

    ``stages == 1`` (the default) keeps the historic 3-axis
    (pod, data, model) mesh — no "stage" axis, so every pspec rule and
    shard_map spec that never mentions it is byte-identical to the
    pre-pipeline layout.  ``stages > 1`` prepends a leading "stage"
    axis: the full coded (pod, data, model) sub-mesh is replicated per
    pipeline stage and activations flow stage→stage via ppermute.
    """
    need = stages * pods * data * model
    have = len(jax.devices())
    if have < need:
        raise ValueError(
            f"mesh ({stages}×{pods}×{data}×{model}) needs {need} "
            f"devices, have "
            f"{have}; set XLA_FLAGS=--xla_force_host_platform_device_count={need}"
        )
    if stages <= 1:
        return auto_mesh((pods, data, model), ("pod", "data", "model"))
    return auto_mesh(
        (stages, pods, data, model), ("stage", "pod", "data", "model")
    )


def auto_mesh(shape, names):
    """``jax.make_mesh`` with every axis Auto: the pspec rules place
    arrays through ``with_sharding_constraint``, which Explicit axes
    (``jax.make_mesh``'s default in jax 0.9) refuse."""
    auto = (jax.sharding.AxisType.Auto,) * len(shape)
    return jax.make_mesh(shape, names, axis_types=auto)


def make_serve_mesh(model: int, data: int = 1):
    """Serving mesh: tensor-parallel "model" axis (+ optional batch
    "data" axis), no pod layer — serving has no coded aggregation, but
    it partitions from the SAME pspec rules as training (canonical axis
    names, so ``dist.sharding`` applies unchanged)."""
    return make_test_mesh(1, data, model)
