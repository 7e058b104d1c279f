"""Runner of the ``serve`` traffic kind: a closed loop of generate calls.

Set-up builds a serve-only ``CodedSession`` and hands it weights made
from the seed by the configuration's reference initializer.  Each of
the ``clients`` (one, in order) sends a request of ``batch`` prompts of
``prompt_len`` seeded token ids and waits for ``gen_len`` greedy
tokens, through ``serving.generate_tokens`` with the session's own
compiled prefill and decode; the decode the harness passes in stamps
the host clock at each call, which is when the previous token reached
the host.  One request of the same shapes warms both programs up.

Afterwards the program is freed and the reference (float32, HIGHEST)
reads, for a seeded sample of the finished requests, how far each
served token's logit lies below the reference's best at its position.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from chipbench import common, refmath, spec


def prompts(seed: int, i: int, batch: int, length: int, vocab: int,
            tag: int = common.PROMPTS) -> np.ndarray:
    return common.np_rng(seed, tag, i).integers(
        0, vocab, (batch, length), dtype=np.int32)


def run(cell, seed: int, seconds: float, record, trace_dir=None) -> Dict:
    import jax
    import jax.numpy as jnp

    from repro.api import CodedSession, serving

    t, conf, ref = cell.traffic, cell.config, cell.reference
    cfg = spec.model_config(conf)
    B, P, G = t["batch"], t["prompt_len"], t["gen_len"]
    if t.get("clients", 1) != 1 or not t.get("greedy", True):
        raise SystemExit("the serve runner runs one greedy client")
    session = CodedSession(None, cfg, seed=common.seed32(seed,
                                                         common.PROGRAM))
    key = common.jax_key(seed, common.WEIGHTS)
    common.check_same_layout(
        jax.eval_shape(lambda k: ref.init_params(k, conf), key),
        session.params, cell.name)
    dtypes = jax.tree.map(lambda a: a.dtype, session.params)
    session.params = None
    gc.collect()
    params = jax.jit(lambda k: jax.tree.map(
        lambda x, d: x.astype(d), ref.init_params(k, conf), dtypes))(key)
    session.params = params

    max_len = P + G + 1  # as CodedSession.generate sizes the cache
    prefill_fn, decode_fn, _ = session._serve_fns(max_len, False)
    cache = jax.eval_shape(prefill_fn, params,
                           jax.ShapeDtypeStruct((B, P), jnp.int32))[1]
    kv = cache["groups"]["p0"]["k"]
    record.values.update(
        batch=B, prompt_len=P, gen_len=G, cache_len=int(kv.shape[-2]),
        cache_itemsize=int(np.dtype(kv.dtype).itemsize),
        q_itemsize=int(np.dtype(cfg.dtype).itemsize))

    stamps: List[float] = []

    def timed_prefill(p, toks):
        with record.span("prefill_dispatch"):
            return prefill_fn(p, toks)

    def timed_decode(p, tok, c):
        stamps.append(time.perf_counter())
        with record.span("decode_dispatch"):
            return decode_fn(p, tok, c)

    def request(ids: np.ndarray) -> np.ndarray:
        return serving.generate_tokens(
            params, cfg, jnp.asarray(ids), G, prefill_fn=timed_prefill,
            decode_fn=timed_decode, greedy=True)

    with record.span("warm_request"):
        request(prompts(seed, 0, B, P, cfg.vocab, common.WARM))
    stamps.clear()

    # ---- the measured window ----------------------------------------
    record.listen_for_compiles()
    compiles0 = record.compiles
    entries0 = prefill_fn._cache_size() + decode_fn._cache_size()
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    done = []
    with record.span("window"):
        t0 = time.perf_counter()
        record.values["window_start"] = t0
        i = 0
        while time.perf_counter() - t0 < seconds:
            ids = prompts(seed, i, B, P, cfg.vocab)
            stamps.clear()
            with record.span("request"):
                toks = request(ids)
            done.append({"i": i, "tokens": toks, "stamps": list(stamps)})
            i += 1
    if trace_dir:
        jax.profiler.stop_trace()
    compiles = (record.compiles - compiles0 + prefill_fn._cache_size()
                + decode_fn._cache_size() - entries0)
    record.values.update(
        token_stamps=[d["stamps"] for d in done],
        memory_peak_bytes=common.memory_peak_bytes(
            jax.devices()[:cell.chips]))
    failed = sum(1 for d in done if d["tokens"].shape != (B, G)
                 or d["tokens"].min() < 0 or d["tokens"].max() >= cfg.vocab)

    # ---- free the program, run the reference ------------------------
    session.params = None
    del session, params, prefill_fn, decode_fn
    gc.collect()
    pick = common.np_rng(seed, common.SAMPLE).choice(
        len(done), size=min(int(t["check_requests"]), len(done)),
        replace=False)
    sample = [(prompts(seed, done[j]["i"], B, P, cfg.vocab),
               done[j]["tokens"]) for j in sorted(pick)]
    record.values["sample"] = sample
    gaps = reference_gaps(cell, seed, sample)
    checks = {"token_gap": max(g["served"] for g in gaps),
              "compiles_in_window": compiles}
    record.values["compared"] = {"requests_checked": len(sample),
                                 "tokens_checked": len(sample) * B * G}
    return {"attempted": len(done), "failed": failed, "checks": checks}


def reference_gaps(cell, seed: int, sample, controls=()) -> List[Dict]:
    """Per sampled request: the widest gap by which a served token's
    reference logit lies below the reference's best at its position;
    and, for each precision policy in ``controls``, the widest gap of
    the token that policy puts first at the same positions."""
    import jax
    import jax.numpy as jnp

    conf, ref = cell.config, cell.reference
    P = cell.traffic["prompt_len"]
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: ref.init_params(k, conf))(
            common.jax_key(seed, common.WEIGHTS))
        fns = {name: jax.jit(
            lambda p, s, e=refmath.POLICIES[name]: ref.logits_at(
                p, conf, s, P - 1, e))
            for name in ("f32",) + tuple(controls)}
        out = []
        for ids, toks in sample:
            seqs = jnp.asarray(np.concatenate([ids, toks[:, :-1]], 1))
            want = fns["f32"](params, seqs)               # (B, G, V)
            best = jnp.max(want, -1)
            served = jnp.take_along_axis(
                want, jnp.asarray(toks)[..., None], -1)[..., 0]
            row = {"served": float(jnp.max(best - served))}
            for name in controls:
                top = jnp.argmax(fns[name](params, seqs), -1)
                got = jnp.take_along_axis(want, top[..., None], -1)[..., 0]
                row[name] = float(jnp.max(best - got))
            out.append(row)
    return out
