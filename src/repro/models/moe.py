"""Mixture-of-Experts layer: top-k routing with capacity, sort-based
dispatch (gather/scatter — no O(N·E·C) one-hot einsums, which would
dwarf the useful expert FLOPs at E=128).

Sharding intent: expert-parallel over the ``model`` mesh axis when
``n_experts`` divides it (llama4's 128e), otherwise experts replicated.
The pjit path leaves the dispatch gathers to SPMD; the dist path
(``ShardCtx`` active, inside shard_map) runs explicit expert
parallelism: the router is column-parallel with its logits all-gathered
(routing and the load-balancing aux loss need the full expert axis),
every shard dispatches only to its own expert block, and the partial
expert outputs — plus the column/row-parallel shared-expert branch —
are combined by a single psum over the model axis.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.dist.sharding import NULL_CTX


def init_moe(rng, d: int, ff: int, E: int, n_shared: int, dtype) -> Dict:
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    scale = 0.02
    p = {
        "router": (jax.random.normal(k1, (d, E)) * scale).astype(dtype),
        "we_g": (jax.random.normal(k2, (E, d, ff)) * scale).astype(dtype),
        "we_u": (jax.random.normal(k3, (E, d, ff)) * scale).astype(dtype),
        "we_d": (jax.random.normal(k4, (E, ff, d)) * scale).astype(dtype),
    }
    if n_shared:
        ks = jax.random.split(k5, 3)
        p["ws_g"] = (jax.random.normal(ks[0], (d, ff * n_shared)) * scale
                     ).astype(dtype)
        p["ws_u"] = (jax.random.normal(ks[1], (d, ff * n_shared)) * scale
                     ).astype(dtype)
        p["ws_d"] = (jax.random.normal(ks[2], (ff * n_shared, d)) * scale
                     ).astype(dtype)
    return p


@tracing.scoped("moe")
def moe_ffn(
    params: Dict,
    x: jnp.ndarray,  # (B, S, d)
    top_k: int,
    capacity_factor: float = 1.25,
    ctx=NULL_CTX,
    shared_width: Optional[int] = None,  # global n_shared·ff (TP detect)
    n_experts: Optional[int] = None,     # global E (TP detect)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output (B,S,d), load-balancing aux loss scalar).

    SP (ctx.sp): ``x`` arrives as the local seq block; the layer
    gathers the full sequence ONCE up front — routing, the router
    logits and the load-balancing aux statistics all need every token
    (the aux loss must stay identical across shards) — and the final
    combine reduce-scatters back to the local seq block.
    """
    x = ctx.gather_seq(x)
    B, S, d = x.shape
    N = B * S
    xf = x.reshape(N, d)

    logits = (xf.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    # TP: when the router is column-parallel (E divides tp), gather the
    # expert axis so routing/top-k/aux see all experts (E is small;
    # (N, E) is cheap).  When fit_pspecs dropped the expert sharding
    # (E % tp != 0) the logits are already full-width — gathering again
    # would duplicate experts and corrupt the routing.
    if (ctx.active and n_experts is not None
            and logits.shape[-1] != n_experts):
        logits = ctx.all_gather(logits, axis=-1)
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)  # (N, E)
    top_p, top_e = jax.lax.top_k(probs, top_k)  # (N, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # ---- aux load-balancing loss (Switch-style): E · Σ_e f_e · P_e ----
    pe = probs.mean(axis=0)
    fe = jnp.zeros(E).at[top_e.reshape(-1)].add(1.0) / (N * top_k)
    aux = E * jnp.sum(fe * pe)

    # ---- sort-based dispatch with capacity -----------------------------
    # TP: each shard owns the contiguous expert block [e0, e0+E_local);
    # routing stays global, the dispatch keeps only local experts and
    # the partial outputs are psum'd below.
    E_local = params["we_g"].shape[0]
    experts_sharded = ctx.active and E_local != E
    e0 = ctx.axis_index() * E_local if experts_sharded else 0
    cap = int(max(1, capacity_factor * N * top_k / E))
    flat_e = top_e.reshape(-1)  # (N·k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # rank within expert = index in sorted stream − expert segment start
    counts = jnp.zeros(E, jnp.int32).at[flat_e].add(1)
    seg_start = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]]
    )
    rank = jnp.arange(N * top_k) - seg_start[sorted_e]
    keep = rank < cap
    if experts_sharded:
        keep = keep & (sorted_e >= e0) & (sorted_e < e0 + E_local)
    slot = jnp.where(
        keep, (sorted_e - e0) * cap + rank, E_local * cap
    )  # sentinel last

    tok_of_slot = order // top_k  # original token of each sorted entry
    buf = jnp.zeros((E_local * cap + 1, d), x.dtype)
    buf = buf.at[slot].set(xf[tok_of_slot])
    buf = buf[: E_local * cap].reshape(E_local, cap, d)

    # ---- expert FFN (swiglu), batched over experts ---------------------
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", buf, params["we_g"])
    ) * jnp.einsum("ecd,edf->ecf", buf, params["we_u"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["we_d"])
    out_buf = jnp.concatenate(
        [out_buf.reshape(E_local * cap, d), jnp.zeros((1, d), out_buf.dtype)],
        0,
    )

    # ---- combine: gather back, weight, sum over the k copies -----------
    # per sorted entry: its slot (or sentinel), weight from router
    w_sorted = top_p.reshape(-1)[order]
    contrib = out_buf[slot] * w_sorted[:, None].astype(out_buf.dtype)
    y = jnp.zeros((N, d), out_buf.dtype).at[tok_of_slot].add(contrib)

    # ---- shared experts (llama4) ---------------------------------------
    sh = None
    sh_sharded = False
    if "ws_g" in params:
        sh = jax.nn.silu(xf @ params["ws_g"]) * (xf @ params["ws_u"])
        sh = sh @ params["ws_d"]
        sh_sharded = (ctx.active and shared_width is not None
                      and params["ws_g"].shape[-1] != shared_width)
    # combine with a single collective over the model axis: partial
    # terms (sharded experts / column-row-parallel shared branch) sum
    # inside, replicated terms stay outside.  Under SP the psum becomes
    # a reduce-scatter over seq and replicated terms slice their local
    # seq block — combine at (B, S, d) so the seq axis is addressable.
    partial = [t.reshape(B, S, d)
               for t, p in ((y, experts_sharded), (sh, sh_sharded)) if p]
    full = [t.reshape(B, S, d)
            for t, p in ((y, experts_sharded), (sh, sh_sharded))
            if t is not None and not p]
    if partial:
        terms = [ctx.psum_scatter(partial[0] if len(partial) == 1
                                  else partial[0] + partial[1])]
        terms += [ctx.scatter_seq(t) for t in full]
    else:
        terms = [ctx.scatter_seq(t) for t in full]
    y = terms[0] if len(terms) == 1 else terms[0] + terms[1]
    return y.astype(x.dtype), aux


def moe_ffn_reference(params, x, top_k):
    """Dense oracle: every expert on every token, masked by routing.

    O(N·E) compute — tests only.  No capacity drops (compare with
    capacity_factor large enough that nothing is dropped).
    """
    B, S, d = x.shape
    E = params["router"].shape[1]
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    gate = jnp.zeros_like(probs)
    gate = jax.vmap(lambda g, e, p: g.at[e].set(p))(gate, top_e, top_p)
    h = jax.nn.silu(
        jnp.einsum("nd,edf->enf", xf, params["we_g"])
    ) * jnp.einsum("nd,edf->enf", xf, params["we_u"])
    per_e = jnp.einsum("enf,efd->end", h, params["we_d"])
    y = jnp.einsum("end,ne->nd", per_e, gate.astype(per_e.dtype))
    if "ws_g" in params:
        sh = jax.nn.silu(xf @ params["ws_g"]) * (xf @ params["ws_u"])
        y = y + sh @ params["ws_d"]
    return y.reshape(B, S, d).astype(x.dtype)
