"""Multi-device shard_map collectives (8 CPU host devices, subprocess).

Validates the explicit two-stage coded aggregation (grad_sync) on a
real (2 pods × 2 data × 2 model) device mesh — the form whose
collectives appear in the dry-run HLO.  Runs in a subprocess so the
512-device dry-run flag and the test session's single device never
conflict.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.hgc import HGCCode
    from repro.core.topology import Tolerance, Topology
    from repro.dist.grad_sync import (
        make_coded_allreduce, make_compressed_cross_pod_sum,
        lam_array_from_code,
    )
    from repro.dist.mesh import make_test_mesh

    mesh = make_test_mesh(2, 2, 2)  # pod × data × model
    topo = Topology.uniform(2, 2)   # edge=pod, worker=data group
    code = HGCCode.build(topo, Tolerance(1, 1), K=4, seed=0)

    rng = np.random.default_rng(0)
    g_parts = rng.normal(size=(code.K, 64)).astype(np.float32)
    true = g_parts.sum(0)

    # each (pod=i, data=j) group computes its encoded message G_ij
    msgs = np.stack([
        code.worker_encode(i, j, g_parts)
        for i in range(2) for j in range(2)
    ]).astype(np.float32)  # (4, 64)

    fast_e, fast_w = (0, 1), [(1,), (0,)]   # 1 straggler per edge
    lam = lam_array_from_code(code, fast_e, fast_w, 2, 2)

    # build per-group message tree replicated per group via shard_map:
    # feed each group its own message by sharding a (pods, data, dim)
    # array and reducing with the coded weights.
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.dist.grad_sync import coded_weighted_psum

    def inner(msg_block, lam_block):
        # msg_block: (1, 1, 64) this group's message
        return coded_weighted_psum(
            {"g": msg_block[0, 0]}, lam_block.reshape(())
        )["g"]

    fn = shard_map(
        inner, mesh=mesh,
        in_specs=(P("pod", "data", None), P("pod", "data")),
        out_specs=P(),
        check_vma=False,
    )
    out = jax.jit(fn)(
        jnp.asarray(msgs.reshape(2, 2, 64)), jnp.asarray(lam)
    )
    err = float(np.max(np.abs(np.asarray(out) - true)))
    assert err < 1e-4, f"coded psum error {err}"
    print("coded_psum_ok", err)

    # hier allreduce == flat sum
    runner = make_coded_allreduce(mesh)
    ones_lam = np.ones((2, 2), np.float32)
    tree = {"a": jnp.ones((8, 8)) * 2.0}
    out2 = jax.jit(lambda t, l: runner(t, l))(tree, jnp.asarray(ones_lam))
    expect = 2.0 * 4  # summed over 2 pods × 2 data groups
    assert np.allclose(np.asarray(out2["a"]), expect), out2["a"][0, 0]
    print("hier_allreduce_ok")

    # compressed cross-pod path ≈ exact
    comp = make_compressed_cross_pod_sum(mesh)
    tree2 = {"a": jnp.asarray(rng.normal(size=(256,)).astype(np.float32))}
    got = jax.jit(lambda t, l: comp(t, l))(tree2, jnp.asarray(ones_lam))
    exact = np.asarray(tree2["a"]) * 4
    rel = np.max(np.abs(np.asarray(got["a"]) - exact)) / np.max(np.abs(exact))
    assert rel < 0.05, rel
    print("compressed_ok", rel)
    """
)


@pytest.mark.parametrize("script", [_SCRIPT], ids=["8dev"])
def test_shard_map_coded_collectives(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "coded_psum_ok" in r.stdout
    assert "hier_allreduce_ok" in r.stdout
    assert "compressed_ok" in r.stdout
