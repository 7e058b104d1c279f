"""A serve-only session holds its weights in the compute dtype.

``CodedSession(None, cfg)`` rounds the weights to ``cfg.dtype`` once, at
construction; the compiled prefill and decode then find nothing to cast.
The operands of every matmul are the ones the per-call cast of the
float32 weights gives, so tokens and logits are exactly those of the
float32 weights cast on every call.

The float32 weights compared against are ``init_params`` compiled as
one program, as the session builds its own.  Eager ``init_params`` runs
op by op; compiled whole, XLA folds ``jax.random.normal``'s sqrt(2)
into the init scale and evaluates constant logs itself, which moves
some float32 values by one ulp.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import CodedSession
from repro.configs.registry import get_smoke_config
from repro.models import transformer as tf

SEED = 3
ARCHS = ("starcoder2-3b", "mamba2-370m", "granite-moe-3b-a800m",
         "recurrentgemma-2b", "granite-8b", "qwen2-vl-2b",
         "whisper-medium", "llama3-8b")


def _leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


def _f32_init(cfg):
    return jax.jit(lambda k: tf.init_params(k, cfg))(
        jax.random.PRNGKey(SEED))


@pytest.mark.parametrize("arch,dtype", [(a, "bfloat16") for a in ARCHS]
                         + [("starcoder2-3b", "float32")])
def test_serve_only_weights_are_the_cast_init(arch, dtype):
    """Held in the compute dtype, and equal to the cast of the float32
    init; under float32 compute (``launch/serve.py --f32``) they stay
    the float32 init."""
    cfg = dataclasses.replace(get_smoke_config(arch), dtype=dtype)
    held = CodedSession(None, cfg, seed=SEED).params
    f32 = _f32_init(cfg)
    want = tf.cast_params(f32, cfg)
    assert jax.tree.structure(held) == jax.tree.structure(want)
    for (path, h), w, f in zip(_leaves(held), jax.tree.leaves(want),
                               jax.tree.leaves(f32)):
        cast = f.ndim >= 2 and f.dtype == jnp.float32
        assert h.dtype == (jnp.dtype(dtype) if cast else f.dtype), path
        np.testing.assert_array_equal(np.asarray(h), np.asarray(w),
                                      err_msg=str(path))


@pytest.mark.parametrize("arch", ARCHS)
def test_generate_with_held_weights_equals_per_call_cast(arch):
    cfg = get_smoke_config(arch)
    s = CodedSession(None, cfg, seed=SEED)
    rng = jax.random.PRNGKey(1)
    prompts = jax.random.randint(rng, (2, 8), 0, cfg.vocab)
    enc = (jax.random.normal(rng, (2, cfg.enc_len, cfg.d_model))
           if cfg.is_encdec else None)
    held = s.generate(prompts, 5, enc_frames=enc)
    s.params = _f32_init(cfg)
    per_call = s.generate(prompts, 5, enc_frames=enc)
    np.testing.assert_array_equal(held, per_call)


def test_decode_logits_with_held_weights_equal_per_call_cast():
    cfg = get_smoke_config("starcoder2-3b")
    s = CodedSession(None, cfg, seed=SEED)
    f32 = _f32_init(cfg)
    prefill, decode, _ = s._serve_fns(16, False)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab)
    tok = jnp.ones((2, 1), jnp.int32)
    out = {}
    for name, params in (("held", s.params), ("f32", f32)):
        logits0, cache = prefill(params, prompts)
        logits1, _ = decode(params, tok, cache)
        out[name] = (np.asarray(logits0), np.asarray(logits1))
    for got, want in zip(out["held"], out["f32"]):
        np.testing.assert_array_equal(got, want)
