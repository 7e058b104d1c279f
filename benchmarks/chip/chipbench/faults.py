"""Faults planted in the program, for the calibration of the limits and
for the tests that see ``correct`` come out false.  Never used by
``run.py``.

Each is a context manager that replaces one function of the program
while it is open; open it before the session is built, since the
compiled steps capture what they call when they are first traced.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(module, name: str, make):
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def state_unchanged():
    """The train step returns the parameters and optimizer state it was
    given."""
    from repro.launch import steps

    def make(orig):
        def build(*a, **kw):
            step = orig(*a, **kw)

            def frozen(params, opt_state, *rest):
                out = step(params, opt_state, *rest)
                return (params, opt_state) + tuple(out[2:])
            return frozen
        return build

    stack = contextlib.ExitStack()
    stack.enter_context(_patched(steps, "_make_dist_train_step", make))
    stack.enter_context(_patched(steps, "make_train_step", make))
    return stack


def half_batch():
    """Every other row of the coded batch is left out and the rest
    count double: the mean over half the batch."""
    from repro.api import session

    def make(orig):
        def build(*a, **kw):
            b = orig(*a, **kw)
            w = b["weights"].copy()
            w[1::2] = 0.0
            w[0::2] *= 2.0
            return dict(b, weights=w)
        return build

    return _patched(session, "build_coded_batch", make)


def no_exchange():
    """The coded decode leaves out the exchange between chips: each
    chip keeps its own lambda-weighted message."""
    import jax

    from repro.dist import grad_sync

    def make(orig):
        def local(tree, lam, axes=None):
            return jax.tree.map(lambda x: x * lam.astype(x.dtype), tree)
        return local

    return _patched(grad_sync, "coded_weighted_psum", make)


def ssd_state_dropped():
    """The chunked SSD scan drops the state between chunks: every chunk
    starts from a zero state, as if it began the sequence."""
    from repro.models import ssm

    def make(orig):
        def chunked(xbar, logdA, Bc, Cc, chunk, h0=None):
            B, S = xbar.shape[:2]
            c = S // chunk

            def split(a):
                return a.reshape(B * c, chunk, *a.shape[2:])

            y, h = orig(split(xbar), split(logdA), split(Bc), split(Cc),
                        chunk)
            return y.reshape(xbar.shape), h.reshape(B, c, *h.shape[1:])[:, -1]
        return chunked

    return _patched(ssm, "ssd_chunked", make)


def frozen_cache():
    """The decode step returns the cache it was given."""
    from repro.models import transformer

    def make(orig):
        def step(params, cfg, token, cache, use_pallas=None):
            return orig(params, cfg, token, cache, use_pallas)[0], cache
        return step

    return _patched(transformer, "decode_step", make)


def altered_token():
    """The decode steps at cache positions 3 mod 8 put a neighbouring
    vocabulary id first."""
    import jax.numpy as jnp

    from repro.api import serving

    def make(orig):
        def build(cfg, use_pallas=None):
            fn = orig(cfg, use_pallas)

            def decode(params, token, cache):
                logits, new = fn(params, token, cache)
                hit = cache["length"] % 8 == 3
                return jnp.where(hit, jnp.roll(logits, 1, -1), logits), new
            return decode
        return build

    return _patched(serving, "make_decode_fn", make)


TRAIN = {"state_unchanged": state_unchanged, "half_batch": half_batch,
         "no_exchange": no_exchange, "ssd_state_dropped": ssd_state_dropped}
SERVE = {"frozen_cache": frozen_cache, "altered_token": altered_token}
