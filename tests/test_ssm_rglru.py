"""Mamba-2 SSD and RG-LRU: chunked/scan forms vs naive recurrences."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: fixed-example fallback
    from repro._hypothesis_fallback import (
        given, settings, strategies as st,
    )

from repro.configs.base import ModelConfig
from repro.models import rglru as R
from repro.models import ssm as S


def _ssd_inputs(seed, B, Sq, nh, hd, N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    xbar = jax.random.normal(ks[0], (B, Sq, nh, hd)) * 0.5
    logdA = -jax.nn.softplus(jax.random.normal(ks[1], (B, Sq, nh)))
    Bc = jax.random.normal(ks[2], (B, Sq, N)) * 0.5
    Cc = jax.random.normal(ks[3], (B, Sq, N)) * 0.5
    return xbar, logdA, Bc, Cc


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 50),
    B=st.integers(1, 2),
    chunks=st.sampled_from([(8, 2), (16, 4), (16, 8)]),
)
def test_ssd_chunked_equals_recurrence(seed, B, chunks):
    Sq, chunk = chunks
    xbar, logdA, Bc, Cc = _ssd_inputs(seed, B, Sq, nh=2, hd=4, N=4)
    y_chunk, h_chunk = S.ssd_chunked(xbar, logdA, Bc, Cc, chunk=chunk)
    y_ref, h_ref = S.ssd_reference(xbar, logdA, Bc, Cc)
    np.testing.assert_allclose(y_chunk, y_ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(h_chunk, h_ref, rtol=1e-4, atol=1e-5)


def test_ssd_chunked_with_initial_state():
    xbar, logdA, Bc, Cc = _ssd_inputs(7, 1, 16, 2, 4, 4)
    h0 = jax.random.normal(jax.random.PRNGKey(9), (1, 2, 4, 4))
    y_c, h_c = S.ssd_chunked(xbar, logdA, Bc, Cc, chunk=4, h0=h0)
    y_r, h_r = S.ssd_reference(xbar, logdA, Bc, Cc, h0=h0)
    np.testing.assert_allclose(y_c, y_r, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(h_c, h_r, rtol=1e-4, atol=1e-5)


def _ssm_cfg():
    return ModelConfig(
        name="t", family="ssm", n_layers=1, d_model=32, n_heads=0,
        n_kv_heads=0, head_dim=1, d_ff=0, vocab=8,
        block_pattern=("ssm",), d_state=8, expand=2, ssm_head_dim=8,
        ssm_chunk=4,
    )


def test_ssm_decode_chain_matches_forward():
    """Feeding tokens one-by-one through ssm_decode_step reproduces the
    full-sequence ssm_forward output at every position."""
    cfg = _ssm_cfg()
    rng = jax.random.PRNGKey(0)
    p = S.init_ssm(rng, cfg.d_model, cfg.expand, cfg.d_state, cfg.d_conv,
                   cfg.ssm_head_dim, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model)) * 0.5
    full = S.ssm_forward(p, x, cfg)
    cache = S.ssm_init_cache(cfg, 2)
    outs = []
    for t in range(8):
        o, cache = S.ssm_decode_step(p, x[:, t : t + 1], cache, cfg)
        outs.append(o)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(full, step, rtol=2e-4, atol=2e-4)


def test_rglru_scan_matches_step_chain():
    cfg = ModelConfig(
        name="t", family="hybrid", n_layers=1, d_model=16, n_heads=2,
        n_kv_heads=1, d_ff=32, vocab=8,
        block_pattern=("recurrent",), lru_width=16,
    )
    p = R.init_rglru_block(
        jax.random.PRNGKey(0), cfg.d_model, cfg.lru_width, cfg.d_conv,
        jnp.float32,
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, cfg.d_model)) * 0.5
    full = R.rglru_block_forward(p, x, cfg)
    cache = R.rglru_init_cache(cfg, 2)
    outs = []
    for t in range(10):
        o, cache = R.rglru_block_step(p, x[:, t : t + 1], cache, cfg)
        outs.append(o)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(full, step, rtol=2e-4, atol=2e-4)


def test_rglru_decay_stability():
    """|a_t| < 1 everywhere ⇒ bounded hidden states on long sequences."""
    p = R.init_rglru_block(jax.random.PRNGKey(0), 8, 8, 4, jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 512, 8))
    h, _ = R.rglru_scan(p, y)
    assert jnp.all(jnp.isfinite(h))
    assert float(jnp.max(jnp.abs(h))) < 100.0


@pytest.mark.parametrize("Sq,chunk,decay", [
    (16, 4, 1.0),
    # mamba2-370m's chunk with its strongest head's decay (A = -16):
    # the masked upper triangle of the segment sums overflows exp()
    (256, 256, 16.0),
])
def test_ssd_gradients_finite(Sq, chunk, decay):
    xbar, logdA, Bc, Cc = _ssd_inputs(3, 1, Sq, 2, 4, 4)
    logdA = logdA * decay

    def loss(xb, la):
        y, _ = S.ssd_chunked(xb, la, Bc, Cc, chunk=chunk)
        return jnp.sum(y**2)

    gx, ga = jax.grad(loss, argnums=(0, 1))(xbar, logdA)
    assert jnp.all(jnp.isfinite(gx))
    assert jnp.all(jnp.isfinite(ga))
