"""Plain float32 reference of mamba2-370m as ``mamba2-370m.json`` runs it.

Mamba-2 (arXiv:2405.21060) with the departures the configuration file
lists: no gated RMSNorm before ``out_proj`` and an RMSNorm epsilon of
``norm_eps``.  The SSD layer is computed in its full-sequence quadratic
(dual) form, ``y = (L o C B^T) x_bar`` with the exact segment-sum decay
matrix ``L``, and not chunk by chunk as the program does.  Parameters
are held in the program's layout (stacked layers under
``groups/p0``), which is how the benchmark hands the same seeded
weights to both.  Nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import refmath


def _sizes(m):
    d, N = m["d_model"], m["d_state"]
    di = m["expand"] * d
    return d, di, N, di // m["ssm_head_dim"], m["ssm_head_dim"]


def init_params(key, cfg):
    """Seeded weights in the program's layout, float32, drawn as
    mamba_ssm's Mamba2 and MixerModel initialise them: PyTorch's
    default ``nn.Linear`` and ``nn.Conv1d`` draws (uniform in
    +-1/sqrt(fan_in), the depthwise conv's fan-in being d_conv),
    ``out_proj`` divided by sqrt(n_layers) (prenorm residual rescale),
    a normal(0, 0.02) embedding, A uniform in [1, 16], dt log-uniform
    in [0.001, 0.1], D and norm scales 1."""
    m = cfg["model"]
    L, V, K = m["n_layers"], m["vocab"], m["d_conv"]
    d, di, N, nh, _ = _sizes(m)
    ks = jax.random.split(key, 12)

    def uniform(k, shape, fan_in, scale=1.0):
        b = scale / fan_in ** 0.5
        return jax.random.uniform(k, shape, jnp.float32, -b, b)

    a = jax.random.uniform(ks[8], (L, nh), jnp.float32, 1.0, 16.0)
    u = jax.random.uniform(ks[9], (L, nh), jnp.float32)
    dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    dt = jnp.maximum(dt, 1e-4)
    ssm = {
        "zproj": uniform(ks[0], (L, d, di), d),
        "xproj": uniform(ks[1], (L, d, di), d),
        "bcproj": uniform(ks[2], (L, d, 2 * N), d),
        "dtproj": uniform(ks[3], (L, d, nh), d),
        "conv_x_w": uniform(ks[4], (L, K, di), K),
        "conv_x_b": uniform(ks[10], (L, di), K),
        "conv_bc_w": uniform(ks[5], (L, K, 2 * N), K),
        "conv_bc_b": uniform(ks[11], (L, 2 * N), K),
        "A_log": jnp.log(a),
        "D": jnp.ones((L, nh), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "out_proj": uniform(ks[6], (L, di, d), di, L ** -0.5),
    }
    return {
        "embed": {"table": jax.random.normal(ks[7], (V, d), jnp.float32)
                  * 0.02},
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "groups": {"p0": {"norm1": {"scale": jnp.ones((L, d), jnp.float32)},
                          "ssm": ssm}},
    }


def _conv(seq, w, b):
    """Causal depthwise conv: out[t] = sum_k w[k] seq[t - K + 1 + k] + b."""
    K, S = w.shape[0], seq.shape[1]
    pad = jnp.pad(seq, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(pad[:, k:k + S] * w[k] for k in range(K)) + b


def _segsum(a):
    """seg[..., i, j] = sum_{t=j+1..i} a[..., t] for j <= i, else -inf."""
    S = a.shape[-1]
    rep = jnp.broadcast_to(a[..., :, None], a.shape + (S,))
    rep = jnp.where(jnp.tril(jnp.ones((S, S), bool), -1), rep, 0.0)
    seg = jnp.cumsum(rep, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((S, S), bool)), seg, -jnp.inf)


def _ssd(xbar, a, Bm, Cm, einsum):
    """Full-sequence SSD, quadratic form.  xbar (B,S,h,p), a (B,S,h)."""
    Lmat = jnp.exp(_segsum(jnp.moveaxis(a, -1, -2)))       # (B,h,S,S)
    G = einsum("bin,bjn->bij", Cm, Bm)                      # (B,S,S)
    return einsum("bhij,bjhp->bihp", Lmat * G[:, None], xbar)


def _layer(p, x, cfg, einsum):
    m = cfg["model"]
    d, di, N, nh, hd = _sizes(m)
    s = p["ssm"]
    h = refmath.rms_norm(x, p["norm1"]["scale"], cfg["norm_eps"])
    z = einsum("bsd,de->bse", h, s["zproj"])
    xs = einsum("bsd,de->bse", h, s["xproj"])
    bc = einsum("bsd,de->bse", h, s["bcproj"])
    dt = einsum("bsd,de->bse", h, s["dtproj"])
    xs = jax.nn.silu(_conv(xs, s["conv_x_w"], s["conv_x_b"]))
    bc = jax.nn.silu(_conv(bc, s["conv_bc_w"], s["conv_bc_b"]))
    Bm, Cm = bc[..., :N], bc[..., N:]
    dt = jax.nn.softplus(dt + s["dt_bias"])
    A = -jnp.exp(s["A_log"])
    xh = xs.reshape(*xs.shape[:2], nh, hd)
    y = _ssd(xh * dt[..., None], dt * A, Bm, Cm, einsum)
    y = y + s["D"][:, None] * xh
    y = y.reshape(*x.shape[:2], di) * jax.nn.silu(z)
    return x + einsum("bse,ed->bsd", y, s["out_proj"])


def hidden(params, cfg, tokens, einsum, residual=jnp.float32):
    """Final-norm hidden states (B, S, d).  ``residual``: the dtype the
    residual stream is rounded to after the embedding and each layer."""

    def rnd(x):
        return x.astype(residual).astype(jnp.float32)

    x = rnd(params["embed"]["table"][tokens])

    layer = jax.checkpoint(lambda p, x: _layer(p, x, cfg, einsum))

    def body(x, p):
        return rnd(layer(p, x)), None

    x, _ = jax.lax.scan(body, x, params["groups"]["p0"])
    return refmath.rms_norm(x, params["final_norm"]["scale"],
                            cfg["norm_eps"])


def nll_sum(params, cfg, tokens, targets, einsum, **kw):
    """Sum over every token of the next-token negative log-likelihood."""
    x = hidden(params, cfg, tokens, einsum, **kw)
    logits = einsum("bsd,vd->bsv", x, params["embed"]["table"])
    return jnp.sum(refmath.token_nll(logits, targets))


def train_flops_per_token(cfg, seq_len: int) -> float:
    """Forward + backward FLOPs per trained token, without recompute.

    Projections, conv and head as 2 x multiply-adds; the SSD as the
    chunked algorithm of the configuration (chunk Q) counts them: the
    causal half of the intra-chunk C B^T and (L o C B^T) x products,
    the chunk-state build and the state read-out.  Backward = 2 x
    forward.
    """
    m = cfg["model"]
    d, di, N, nh, hd = _sizes(m)
    Q = min(m["ssm_chunk"], seq_len)
    proj = 2 * d * (2 * di + 2 * N + nh) + 2 * di * d
    conv = 2 * m["d_conv"] * (di + 2 * N)
    ssd = Q * N + Q * di + 2 * di * N + 2 * di * N
    fwd = m["n_layers"] * (proj + conv + ssd) + 2 * d * m["vocab"]
    return 3.0 * fwd

