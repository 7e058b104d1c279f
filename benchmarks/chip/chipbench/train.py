"""Runner of the ``train`` traffic kind: coded training steps.

Set-up builds one ``CodedSession`` (the compiled step and its sharded
state), hands it weights made from the seed by the configuration's
reference initializer, and feeds it through its own ``build_batch``
from a seeded per-part source: every replica of part ``k`` at step
``t`` carries the same rows, as the coding assumes.  Set-up then drives
the first ``reference_steps`` steps through ``session.step()``, the
call the window uses, and keeps what the comparison needs: each step's
loss, the first gradient as the optimizer received it (from AdamW's
first moment after one step) and each leaf's change after those steps.
The window runs ``session.step()`` until ``seconds`` have passed.
Afterwards the program's state is freed and the reference, in float32
at ``HIGHEST`` precision, follows the same steps on the same rows.
"""
from __future__ import annotations

import copy
import gc
import time
from typing import Dict, List

import numpy as np

from chipbench import common, refmath, spec


class PartFeed:
    """Seeded rows of each dataset part, per step: (part_batch, seq+1)
    uniform token ids, split into inputs and next-token targets."""

    def __init__(self, seed: int, part_batch: int, seq_len: int,
                 vocab: int):
        self.seed, self.pb, self.seq, self.vocab = seed, part_batch, \
            seq_len, vocab
        self.step = 0

    def rows(self, step: int, part: int) -> np.ndarray:
        rng = common.np_rng(self.seed, common.DATA, step, part)
        return rng.integers(0, self.vocab, (self.pb, self.seq + 1),
                            dtype=np.int32)

    def part(self, k: int) -> "_PartStream":
        return _PartStream(self, k)


class _PartStream:
    """The session's per-part stream interface (``next_batch``)."""

    def __init__(self, feed: PartFeed, k: int):
        self.feed, self.k = feed, k

    def next_batch(self) -> Dict[str, np.ndarray]:
        r = self.feed.rows(self.feed.step, self.k)
        return {"tokens": r[:, :-1], "targets": r[:, 1:],
                "weights": np.ones(r[:, 1:].shape, np.float32)}


def count_replica_defect(session, seq_len: int) -> Dict[str, int]:
    """Replica rows of one part that differ from the part's first copy
    when the session's own streams feed ``build_coded_batch``."""
    from repro.api.session import build_coded_batch

    code, topo = session.code, session.cluster.topo
    fast_e = tuple(range(topo.n))
    fast_w = [tuple(range(topo.m[i])) for i in range(topo.n)]
    b = build_coded_batch(code, copy.deepcopy(session.streams), fast_e,
                          fast_w, seq_len, with_lam=False)
    pb = session.part_batch
    first: Dict[int, np.ndarray] = {}
    row, differ, replicas = 0, 0, 0
    for i in range(topo.n):
        for j in range(topo.m[i]):
            for k in code.assignment.worker_parts(i, j):
                rows = b["tokens"][row:row + pb]
                row += pb
                if k not in first:
                    first[k] = rows
                    continue
                replicas += pb
                differ += int(np.sum(np.any(rows != first[k], axis=1)))
    return {"replica_rows": replicas, "differing": differ}


def run(cell, seed: int, seconds: float, record, trace_dir=None) -> Dict:
    import jax
    import jax.numpy as jnp

    from repro.api import CodedCluster, CodedSession, planner_for_scheme

    t, conf, ref = cell.traffic, cell.config, cell.reference
    cfg = spec.model_config(conf)
    session = CodedSession(
        CodedCluster.homogeneous(t["edges"], t["workers"]), cfg,
        planner=planner_for_scheme(t["scheme"], t["s_e"], t["s_w"]),
        scheme=t["scheme"], mode=t["mode"], seq_len=t["seq_len"],
        part_batch=t["part_batch"], optimizer=t["optimizer"], lr=t["lr"],
        total_steps=t["total_steps"], warmup_steps=0,
        grad_clip=t["grad_clip"], seed=common.seed32(seed, common.PROGRAM),
        log_every=1 << 30, verbose=False,
    )
    code, topo = session.code, session.cluster.topo
    K, pb, seq = code.K, t["part_batch"], t["seq_len"]
    if K != t["parts"]:
        raise SystemExit(f"the code splits the data into K={K} parts; "
                         f"the traffic states {t['parts']}")
    rows_per_step = code.load * sum(topo.m) * pb

    defect = count_replica_defect(session, seq)
    print(f"[defect] the session's own TokenStreams give "
          f"{defect['differing']} of {defect['replica_rows']} replica rows "
          f"that differ from their part's first copy (next_batch advances "
          f"per call); the window feeds seeded rows per (step, part)",
          flush=True)

    # weights: the reference initializer, in the program's layout and
    # shardings, made on the device in one call
    key = common.jax_key(seed, common.WEIGHTS)
    common.check_same_layout(
        jax.eval_shape(lambda k: ref.init_params(k, conf), key),
        session.params, cell.name)
    shardings = jax.tree.map(lambda a: a.sharding, session.params)
    dtypes = jax.tree.map(lambda a: a.dtype, session.params)
    session.params = None
    gc.collect()
    session.params = jax.jit(
        lambda k: jax.tree.map(lambda x, d: x.astype(d),
                               ref.init_params(k, conf), dtypes),
        out_shardings=shardings)(key)

    feed = PartFeed(seed, pb, seq, cfg.vocab)
    session.streams = [feed.part(k) for k in range(K)]
    build = session.build_batch

    def timed_build(fast_e, fast_w):
        feed.step = session._step
        with record.span("build_batch"):
            return build(fast_e, fast_w)

    session.build_batch = timed_build

    # the first steps, through the window's own call; keep what the
    # reference is compared on
    n_ref = int(t["reference_steps"])
    p0 = session.params
    for s in range(n_ref):
        with record.span("warm_step"):
            session.step()
        if s == 0:
            if not (isinstance(session.opt_state, dict)
                    and "m" in session.opt_state):
                raise SystemExit("the optimizer state holds no first "
                                 "moment 'm' to read the gradient from")
            grad_prog = common.leaf_norms(jax.tree.map(
                lambda m: m / (1.0 - refmath.ADAM_B1),
                session.opt_state["m"]))
    change_prog = common.leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        session.params, p0))
    del p0
    loss_prog = list(session.losses[:n_ref])

    # ---- the measured window ----------------------------------------
    record.listen_for_compiles()
    compiles0, entries0 = record.compiles, session.jit_cache_entries()
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    losses: List[float] = []
    with record.span("window"):
        t0 = time.perf_counter()
        record.values["window_start"] = t0
        while time.perf_counter() - t0 < seconds:
            with record.span("step"):
                losses.append(float(session.step()["loss"]))
    if trace_dir:
        jax.profiler.stop_trace()
    compiles = (record.compiles - compiles0
                + session.jit_cache_entries() - entries0)
    devices = jax.devices()[:cell.chips]
    record.values.update(
        steps=len(losses), useful_tokens_per_step=K * pb * seq,
        rows_per_step=rows_per_step, seq_len=seq,
        memory_peak_bytes=common.memory_peak_bytes(devices))
    failed = sum(1 for x in losses if not np.isfinite(x))

    # ---- free the program, run the reference ------------------------
    session.params = session.opt_state = session.train_step = None
    del session
    gc.collect()
    record.values["feed"] = feed
    loss_ref, grad_ref, change_ref = reference(cell, seed, feed, n_ref)
    record.values["reference"] = (loss_ref, grad_ref, change_ref)
    keep = common.moving_leaves(grad_ref)
    grad = common.worst_leaf_gap(grad_prog, grad_ref)
    change = common.worst_leaf_gap(change_prog, change_ref, keep)
    checks = {"loss_gap": common.rel_gap(loss_prog, loss_ref),
              "grad_gap": grad["value"], "change_gap": change["value"],
              "compiles_in_window": compiles}
    record.values["compared"] = {
        "loss_prog": loss_prog, "loss_ref": loss_ref,
        "grad_worst_leaf": grad["leaf"], "change_worst_leaf": change["leaf"],
        "leaves_left_out": sorted(set(grad_ref) - set(keep)),
        "grad_ref_over_median": common.over_median(grad_ref)}
    return {"attempted": len(losses), "failed": failed, "checks": checks}


def reference(cell, seed: int, feed: PartFeed, n_steps: int,
              policy: str = "f32", rows=None):
    """The reference's first ``n_steps`` coded-free steps on the rows
    the program was fed: per-step losses, the first (clipped) gradient's
    leaf norms and each leaf's change after ``n_steps``.

    ``rows(step, part)`` overrides the rows (faults planted in the
    reference use it); ``policy`` picks the precision
    (:func:`refmath.policy`)."""
    import jax
    import jax.numpy as jnp

    t, conf, ref = cell.traffic, cell.config, cell.reference
    einsum, kw = refmath.policy(policy)
    rows = rows or feed.rows
    K = int(t["parts"])
    denom = float(K * t["part_batch"] * t["seq_len"])

    def loss(params, r):
        return ref.nll_sum(params, conf, r[:, :-1], r[:, 1:], einsum,
                           **kw) / denom

    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(jax.value_and_grad(loss))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        params = jax.jit(lambda k: ref.init_params(k, conf))(
            common.jax_key(seed, common.WEIGHTS))
        p0 = params
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        opt = jax.jit(refmath.adamw_step, static_argnums=(4, 5))
        clip = jax.jit(refmath.clip_global, static_argnums=(1,))
        losses, grad_norms = [], None
        for s in range(n_steps):
            total, grads = 0.0, None
            for k in range(K):
                lk, gk = grad_fn(params, jnp.asarray(rows(s, k)))
                total += float(lk)
                grads = gk if grads is None else add(grads, gk)
            losses.append(total)
            grads = clip(grads, float(t["grad_clip"]))
            if s == 0:
                grad_norms = common.leaf_norms(grads)
            lr = refmath.cosine_lr(t["lr"], t["total_steps"], s)
            params, m, v = opt(params, grads, m, v, s + 1, lr)
        change = common.leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return losses, grad_norms, change

