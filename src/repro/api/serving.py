"""Serving building blocks: compiled prefill → decode handoff + sampling.

Two prefill lowerings, both single-dispatch jittable functions (the
session shards them onto a tensor-parallel mesh by pinning in/out
shardings — the function bodies never change):

  * **bulk** (default): one ``tf.prefill`` forward over the whole
    prompt, re-laid into the decode ring buffers by
    ``tf.prefill_to_decode_cache`` — S× fewer dispatches and a
    matmul-shaped lowering instead of S sequential decode steps,
  * **exact** (``exact=True``, and the automatic fallback for archs
    whose recurrent/cross-attention states only exist on the decode
    path): the prompt fed through ``decode_step`` one token at a time —
    inside one ``lax.scan``, so even the debug path compiles once.
"""
from __future__ import annotations

import itertools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import tracing
from repro.configs.base import ModelConfig
from repro.models import transformer as tf


def make_prefill_fn(
    cfg: ModelConfig,
    max_len: int,
    *,
    exact: bool = False,
    dtype: str = "float32",
) -> Callable:
    """→ ``prefill(params, tokens[, enc_frames]) → (last_logits, cache)``.

    ``cache`` is in ``decode_step`` layout either way; ``last_logits``
    is ``(B, V)`` — the logits the first generated token samples from.
    """
    use_bulk = tf.bulk_prefill_supported(cfg) and not exact

    def bulk(params, tokens, enc_frames=None):
        logits, pcache = tf.prefill(params, cfg, tokens, last_only=True)
        cache = tf.prefill_to_decode_cache(cfg, pcache, max_len,
                                           dtype=dtype)
        return logits[:, -1], cache

    def exact_loop(params, tokens, enc_frames=None):
        B, S = tokens.shape
        cache = tf.init_cache(cfg, B, max_len, dtype=dtype)
        if cfg.is_encdec:
            cache = tf.fill_cross_cache(params, cfg, enc_frames, cache)

        def body(cache, tok):
            logits, cache = tf.decode_step(params, cfg, tok[:, None],
                                           cache)
            return cache, logits

        cache, logits = lax.scan(body, cache, jnp.moveaxis(tokens, 1, 0))
        return logits[-1], cache

    return bulk if use_bulk else exact_loop


def make_decode_fn(
    cfg: ModelConfig, use_pallas: Optional[bool] = None
) -> Callable:
    """→ ``decode(params, token, cache) → (logits, cache)``.

    ``use_pallas`` gates the fused ring-buffer decode-attention kernel
    in the serve hot loop (None ⇒ auto: compiled kernel on TPU, XLA
    attention elsewhere; True forces the kernel — interpret mode
    off-TPU, the parity path CI exercises).  The switch is resolved
    ONCE here so every jitted decode dispatch takes the same path.
    """
    if use_pallas is None:
        from repro.kernels.ops import on_tpu

        use_pallas = on_tpu()

    def decode(params, token, cache):
        return tf.decode_step(params, cfg, token, cache,
                              use_pallas=use_pallas)

    return decode


#: request ids of the ``serve.request`` spans, unique in the process
_request_ids = itertools.count()


def generate_tokens(
    params,
    cfg: ModelConfig,
    prompt,
    gen_len: int,
    *,
    prefill_fn: Callable,
    decode_fn: Callable,
    enc_frames=None,
    greedy: bool = True,
    seed: int = 0,
) -> np.ndarray:
    """The generation loop over prebuilt (possibly sharded) step fns.

    Host spans: one ``serve.request`` per call, and inside it
    ``serve.prefill`` (with the first token's argmax), then per token
    ``serve.token_sync`` (the wait for the token), ``serve.decode`` and
    ``serve.sample``, each with the token's index."""
    B, S = np.shape(prompt)
    with tracing.span("serve.request", request=next(_request_ids),
                      batch=B, prompt_len=S):
        with tracing.span("serve.prefill"):
            if cfg.is_encdec:
                logits, cache = prefill_fn(params, prompt, enc_frames)
            else:
                logits, cache = prefill_fn(params, prompt)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        rng = jax.random.PRNGKey(seed)
        out = []
        for i in range(gen_len):
            with tracing.span("serve.token_sync", token=i):
                out.append(np.asarray(tok))
            with tracing.span("serve.decode", token=i):
                logits, cache = decode_fn(params, tok, cache)
            with tracing.span("serve.sample", token=i):
                if greedy:
                    tok = jnp.argmax(logits, -1)[:, None].astype(
                        jnp.int32)
                else:
                    rng, k = jax.random.split(rng)
                    tok = jax.random.categorical(k, logits)[:, None] \
                        .astype(jnp.int32)
        return np.concatenate(out, axis=1)


def prefill_into_cache(
    params,
    cfg: ModelConfig,
    tokens,
    max_len: int,
    enc_frames=None,
    *,
    exact: bool = False,
) -> Tuple[jnp.ndarray, object]:
    """Single-host convenience: jit + run one prefill → (logits, cache)."""
    fn = jax.jit(make_prefill_fn(cfg, max_len, exact=exact))
    if cfg.is_encdec:
        return fn(params, tokens, enc_frames)
    return fn(params, tokens)


def generate(
    params,
    cfg: ModelConfig,
    prompt,
    gen_len: int,
    max_len: Optional[int] = None,
    enc_frames=None,
    greedy: bool = True,
    seed: int = 0,
    exact_handoff: bool = False,
) -> np.ndarray:
    """Single-host generation (the ``CodedSession.generate`` tp=1 path)."""
    prompt = jnp.asarray(prompt, jnp.int32)
    max_len = max_len or prompt.shape[1] + gen_len + 1
    prefill_fn = jax.jit(
        make_prefill_fn(cfg, max_len, exact=exact_handoff)
    )
    decode_fn = jax.jit(make_decode_fn(cfg))
    return generate_tokens(
        params, cfg, prompt, gen_len, prefill_fn=prefill_fn,
        decode_fn=decode_fn, enc_frames=enc_frames, greedy=greedy,
        seed=seed,
    )
