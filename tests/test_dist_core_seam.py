"""The dist ↔ core seam: λ weights and the sharded two-stage decode.

Two contracts keep the JAX execution layer honest against the numpy
reference code construction:

  1. ``grad_sync.lam_array_from_code`` is EXACTLY
     ``HGCCode.collapsed_weights`` laid out on the (pod, data) mesh —
     for both constructions and random tolerated straggler patterns,
  2. the shard_map two-stage coded aggregation reproduces
     ``HGCCode.simulate_iteration`` on a real 8-host-device mesh.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.hgc import HGCCode
from repro.core.topology import Tolerance, Topology
from repro.dist.grad_sync import lam_array_from_code


def _random_tolerated_pattern(rng, topo, tol):
    edges = rng.permutation(topo.n)
    n_dead_e = rng.integers(0, tol.s_e + 1)
    fast_e = tuple(sorted(int(i) for i in edges[: topo.n - n_dead_e]))
    fast_w = []
    for i in range(topo.n):
        order = rng.permutation(topo.m[i])
        n_dead_w = rng.integers(0, tol.s_w + 1)
        fast_w.append(
            tuple(sorted(int(j) for j in order[: topo.m[i] - n_dead_w]))
        )
    return fast_e, fast_w


@pytest.mark.parametrize("construction", ["random", "frc"])
def test_lam_array_matches_collapsed_weights(construction):
    topo = Topology.uniform(4, 4)
    tol = Tolerance(1, 1)
    code = HGCCode.build(topo, tol, K=8, seed=3, construction=construction)
    rng = np.random.default_rng(0)
    for _ in range(25):
        fast_e, fast_w = _random_tolerated_pattern(rng, topo, tol)
        lam2d = lam_array_from_code(code, fast_e, fast_w, 4, 4)
        want = code.collapsed_weights(fast_e, fast_w)
        assert lam2d.shape == (4, 4)
        np.testing.assert_array_equal(
            lam2d.reshape(-1), want.astype(np.float32)
        )


def test_lam_array_rejects_mismatched_mesh():
    topo = Topology.uniform(2, 2)
    code = HGCCode.build(topo, Tolerance(1, 1), K=4, seed=0)
    with pytest.raises(ValueError):
        lam_array_from_code(code, (0, 1), [(0,), (1,)], 2, 4)


def test_lam_zeros_exactly_on_stragglers():
    topo = Topology.uniform(2, 4)
    tol = Tolerance(1, 1)
    code = HGCCode.build(topo, tol, K=8, seed=1)
    fast_e, fast_w = (0,), [(0, 2, 3), (0, 1, 2)]
    lam = lam_array_from_code(code, fast_e, fast_w, 2, 4)
    assert np.all(lam[1] == 0.0)  # straggling edge drops whole pod row
    assert lam[0, 1] == 0.0       # straggling worker within fast edge
    assert np.any(lam[0] != 0.0)


# ----------------------------------------------------------------------
# sharded decode == numpy reference (8 CPU host devices, subprocess so
# this session's single-device jax never conflicts with the flag)
# ----------------------------------------------------------------------
_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.hgc import HGCCode
    from repro.core.topology import Tolerance, Topology
    from jax import shard_map
    from repro.dist.grad_sync import coded_weighted_psum, lam_array_from_code
    from repro.dist.mesh import make_test_mesh

    mesh = make_test_mesh(2, 2, 2)
    topo = Topology.uniform(2, 2)
    tol = Tolerance(1, 1)

    def sim_selection(code, e_str, w_str):
        # mirror simulate_iteration's fast-set truncation exactly
        n, s_e, s_w = code.topo.n, code.tol.s_e, code.tol.s_w
        fast_e = [i for i in range(n) if i not in set(e_str)][: n - s_e]
        fast_w = []
        for i in range(n):
            mi = code.topo.m[i]
            fw = [j for j in range(mi) if j not in set(w_str[i])]
            fast_w.append(tuple(fw[: mi - s_w]) if i in fast_e else ())
        return tuple(fast_e), fast_w

    fn = shard_map(
        lambda m, l: coded_weighted_psum({"g": m[0, 0]}, l.reshape(()))["g"],
        mesh=mesh,
        in_specs=(P("pod", "data", None), P("pod", "data")),
        out_specs=P(),
        check_vma=False,
    )
    fn = jax.jit(fn)

    rng = np.random.default_rng(7)
    for construction in ("random", "frc"):
        code = HGCCode.build(topo, tol, K=4, seed=0,
                             construction=construction)
        g = rng.normal(size=(code.K, 96))
        msgs = np.stack([
            code.worker_encode(i, j, g) for i in range(2) for j in range(2)
        ])
        for e_str, w_str in [
            ((), [(1,), (0,)]),    # 1 worker straggler per edge
            ((0,), [(), (1,)]),    # edge 0 down + 1 worker straggler
            ((), [(), ()]),        # nobody late (sim still truncates)
        ]:
            fast_e, fast_w = sim_selection(code, e_str, w_str)
            lam = lam_array_from_code(code, fast_e, fast_w, 2, 2,
                                      dtype=np.float64)
            want = code.simulate_iteration(g, e_str, w_str)
            got = np.asarray(
                fn(jnp.asarray(msgs.reshape(2, 2, -1)), jnp.asarray(lam))
            )
            np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)
            np.testing.assert_allclose(want, g.sum(0), rtol=1e-7, atol=1e-9)
    print("SEAM_OK")
    """
)


def test_sharded_decode_matches_simulate_iteration():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "SEAM_OK" in r.stdout
