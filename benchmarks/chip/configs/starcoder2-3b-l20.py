"""Plain float32 reference of starcoder2-3b as ``starcoder2-3b-l20.json``
runs it.

StarCoder2 (arXiv:2402.19173): pre-LayerNorm decoder, grouped-query
attention with rotary embeddings (rotate-half), sliding window,
tanh-GELU MLP, tied embedding; with the departures the configuration
file lists (no projection biases, LayerNorm epsilon ``norm_eps``).
Whole-sequence causal attention, no cache and no kernels.  Parameters
are held in the program's layout (stacked layers under ``groups/p0``).
Nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import refmath


def init_params(key, cfg):
    """Seeded weights in the program's layout, float32."""
    m = cfg["model"]
    L, d, V, ff = m["n_layers"], m["d_model"], m["vocab"], m["d_ff"]
    H, Kv, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    ks = jax.random.split(key, 8)

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * 0.02

    def norm(*lead):
        return {"scale": jnp.ones(lead + (d,), jnp.float32),
                "bias": jnp.zeros(lead + (d,), jnp.float32)}

    layers = {
        "norm1": norm(L),
        "attn": {"wq": normal(ks[0], (L, d, H * Dh)),
                 "wk": normal(ks[1], (L, d, Kv * Dh)),
                 "wv": normal(ks[2], (L, d, Kv * Dh)),
                 "wo": normal(ks[3], (L, H * Dh, d))},
        "norm2": norm(L),
        "mlp": {"w1": normal(ks[4], (L, d, ff)),
                "w2": normal(ks[5], (L, ff, d))},
    }
    return {"embed": {"table": normal(ks[6], (V, d))},
            "final_norm": norm(),
            "groups": {"p0": layers}}


def _rope(x, theta):
    """Rotate-half rotary embedding at positions 0..S-1.  x (B,S,h,Dh)."""
    S, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, Dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(p, x, cfg, einsum):
    m = cfg["model"]
    H, Kv, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    B, S, _ = x.shape
    eps = cfg["norm_eps"]
    a = p["attn"]
    h = refmath.layer_norm(x, p["norm1"]["scale"], p["norm1"]["bias"], eps)
    q = einsum("bsd,de->bse", h, a["wq"]).reshape(B, S, Kv, H // Kv, Dh)
    k = einsum("bsd,de->bse", h, a["wk"]).reshape(B, S, Kv, Dh)
    v = einsum("bsd,de->bse", h, a["wv"]).reshape(B, S, Kv, Dh)
    q = _rope(q.reshape(B, S, H, Dh), m["rope_theta"]).reshape(q.shape)
    k = _rope(k, m["rope_theta"])
    s = einsum("bskgd,btkd->bkgst", q, k) / jnp.sqrt(jnp.float32(Dh))
    pos = jnp.arange(S)
    ok = pos[None, :] <= pos[:, None]
    if m.get("window"):
        ok &= pos[:, None] - pos[None, :] < m["window"]
    s = jnp.where(ok, s, -jnp.inf)
    o = einsum("bkgst,btkd->bskgd", jax.nn.softmax(s, axis=-1), v)
    x = x + einsum("bse,ed->bsd", o.reshape(B, S, H * Dh), a["wo"])
    h = refmath.layer_norm(x, p["norm2"]["scale"], p["norm2"]["bias"], eps)
    u = jax.nn.gelu(einsum("bsd,df->bsf", h, p["mlp"]["w1"]),
                    approximate=True)
    return x + einsum("bsf,fd->bsd", u, p["mlp"]["w2"])


def logits_at(params, cfg, tokens, start: int, einsum):
    """Logits (B, S - start, V) at positions start..S-1 of ``tokens``."""
    x = params["embed"]["table"][tokens]

    def body(x, p):
        return _layer(p, x, cfg, einsum), None

    x, _ = jax.lax.scan(body, x, params["groups"]["p0"])
    x = x[:, start:]
    fn = params["final_norm"]
    x = refmath.layer_norm(x, fn["scale"], fn["bias"], cfg["norm_eps"])
    return einsum("bsd,vd->bsv", x, params["embed"]["table"])


def _attn_width(m):
    return m["n_heads"] * m["head_dim"]


def _matmul_params(m):
    """Weights that every token multiplies: projections, MLP, head."""
    d, ff = m["d_model"], m["d_ff"]
    kv = m["n_kv_heads"] * m["head_dim"]
    per_layer = d * _attn_width(m) * 2 + d * kv * 2 + 2 * d * ff
    return m["n_layers"] * per_layer + d * m["vocab"]


def prefill_flops(cfg, batch: int, prompt_len: int) -> float:
    """Forward FLOPs of a prompt: matmuls plus causal attention
    (q.k and p.v over the positions each token may see)."""
    m = cfg["model"]
    S, W = prompt_len, m.get("window") or prompt_len
    seen = sum(min(i + 1, W) for i in range(S))
    attn = 4.0 * _attn_width(m) * seen * m["n_layers"]
    return batch * (2.0 * _matmul_params(m) * S + attn)


def decode_flops(cfg, batch: int, context: int) -> float:
    """Forward FLOPs of one decoded token per sequence that sees
    ``context`` positions (its own included)."""
    m = cfg["model"]
    seen = min(context, m.get("window") or context)
    return batch * (2.0 * _matmul_params(m)
                    + 4.0 * _attn_width(m) * seen * m["n_layers"])
