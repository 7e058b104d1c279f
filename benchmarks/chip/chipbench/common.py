"""Seeds, leaf norms and the comparison numbers that decide ``correct``."""
from __future__ import annotations

import statistics
from typing import Dict, Optional, Sequence

import numpy as np

# tags that keep the streams drawn from one --seed apart
WEIGHTS, DATA, PROMPTS, SAMPLE, PROGRAM, WARM = 1, 2, 3, 4, 5, 6


def np_rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *tags]))


def seed32(seed: int, *tags: int) -> int:
    """A 31-bit seed derived from ``--seed``, for APIs that want one."""
    ss = np.random.SeedSequence([seed, *tags])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def jax_key(seed: int, *tags: int):
    import jax

    return jax.random.PRNGKey(seed32(seed, *tags))


def leaf_norms(tree) -> Dict[str, float]:
    """{leaf path: float32 L2 norm} of a pytree of arrays."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        t))(tree)
    flat, _ = jax.tree_util.tree_flatten_with_path(norms)
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


def check_same_layout(ours, theirs, what: str) -> None:
    """Refuse to go on where the seeded weights do not have the
    program's parameter layout (paths, shapes)."""
    import jax

    def shapes(t):
        flat, _ = jax.tree_util.tree_flatten_with_path(t)
        return {jax.tree_util.keystr(p): tuple(x.shape) for p, x in flat}

    a, b = shapes(ours), shapes(theirs)
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))[:6]
        raise SystemExit(f"{what}: the reference's weight layout differs "
                         f"from the program's: {diff}")


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep: Optional[Sequence[str]] = None) -> Dict:
    """Largest |prog norm - ref norm| over the leaves, each measured
    against max(ref norm of the leaf, median ref norm)."""
    leaves = list(keep if keep is not None else ref)
    med = statistics.median(ref[k] for k in leaves)
    worst, where = 0.0, ""
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not np.isfinite(gap):
            return {"value": float("inf"), "leaf": k}
        if gap > worst:
            worst, where = gap, k
    return {"value": worst, "leaf": where}


def moving_leaves(ref_grad: Dict[str, float], share: float = 1e-3):
    """Leaves whose reference gradient is above ``share`` of the median
    leaf's: the others move under Adam by round-off alone."""
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= share * med]


def over_median(norms: Dict[str, float]) -> Dict[str, float]:
    """Each leaf's norm as a share of the median leaf's."""
    med = statistics.median(norms.values())
    return {k: v / med for k, v in norms.items()}


def rel_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    worst = 0.0
    for p, r in zip(prog, ref):
        g = abs(p - r) / abs(r)
        if not np.isfinite(g):
            return float("inf")
        worst = max(worst, g)
    return worst


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
