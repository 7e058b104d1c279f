"""Plain float32 building blocks shared by the configurations' references.

Nothing here imports the program under test.  Every matrix product of a
reference goes through a *precision policy*, a function with the
signature of ``jnp.einsum``:

* :func:`einsum_f32` — float32 operands at ``Precision.HIGHEST`` (on a
  TPU a float32 product otherwise runs in bfloat16 passes);
* :func:`einsum_bf16` — operands rounded to bfloat16, products summed in
  float32: the compute precision the configurations state;
* :func:`einsum_fp8` — the control: each operand scaled per tensor to
  the float8-e4m3 range, rounded to float8, and multiplied back in
  float32.  It is the step below the bfloat16 compute that the
  configurations state, and it has to come out as not correct.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8  # AdamW's published defaults


def einsum_f32(eq: str, a, b):
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _to_fp8(x):
    """x / scale rounded to float8-e4m3 (per-tensor scale); the
    gradient passes straight through the rounding."""
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX)
    y = x / scale
    q = y.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return y + jax.lax.stop_gradient(q - y), scale


def einsum_fp8(eq: str, a, b):
    qa, sa = _to_fp8(a)
    qb, sb = _to_fp8(b)
    return jnp.einsum(eq, qa, qb, precision=HIGHEST,
                      preferred_element_type=jnp.float32) * (sa * sb)


def einsum_bf16(eq: str, a, b):
    return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


POLICIES = {"f32": einsum_f32, "fp8": einsum_fp8, "bf16": einsum_bf16}


def policy(name: str):
    """``"<einsum policy>[+bf16res]"`` -> (einsum, keyword arguments of
    the reference): ``+bf16res`` rounds the residual stream to bfloat16
    after the embedding and after every layer, as the program keeps
    it."""
    prec, _, res = name.partition("+")
    if res not in ("", "bf16res"):
        raise ValueError(name)
    return POLICIES[prec], ({"residual": jnp.bfloat16} if res else {})


def rms_norm(x, scale, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def token_nll(logits, targets):
    """Per-token negative log-likelihood, float32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - tgt


def cosine_lr(base: float, total: int, step: int) -> float:
    """Cosine decay from ``base`` over ``total`` steps, no warm-up."""
    import math

    prog = min(max(step / max(total, 1), 0.0), 1.0)
    return base * 0.5 * (1.0 + math.cos(math.pi * prog))


def clip_global(grads, max_norm: float):
    """Scale the whole gradient so its global norm is at most max_norm."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / norm)
    return jax.tree.map(lambda g: g * scale, grads)


def adamw_step(params, grads, m, v, t: int, lr: float,
               b1=ADAM_B1, b2=ADAM_B2, eps=ADAM_EPS, weight_decay=0.0):
    """One AdamW update (Loshchilov & Hutter), step count ``t`` >= 1."""
    m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * g * g, v, grads)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def upd(p, mm, vv):
        return p - lr * ((mm / c1) / (jnp.sqrt(vv / c2) + eps)
                         + weight_decay * p)

    return jax.tree.map(upd, params, m, v), m, v
