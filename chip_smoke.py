#!/usr/bin/env python3
"""Smoke run of the coded system on TPU chips, through its normal entry
points.

  python3 chip_smoke.py               # one chip
  python3 chip_smoke.py --four-chips  # the (pod=2, data=2) mesh only

One chip: coded training of mamba2-370m at its published widths and all
48 layers (``CodedSession``, ``--dist off``, 2 edges x 2 workers, hgc
(s_e, s_w) = (1, 1), a forced edge drop, one compiled train step), the
fused dequant-combine kernels against their jnp references at a real
leaf size, and serving of starcoder2-3b at its published widths through
``CodedSession.generate`` (decode through the compiled Pallas
decode-attention kernel, logits checked against the full forward pass).

Four chips: the same mamba2-370m training under ``--dist coded`` and
``--dist coded_q --grad-compression int8`` on a (pod=2, data=2) mesh,
each compared step by step with ``--dist off`` on one device.

Timings print as observations on earlier lines.  The last line is one
JSON object naming the device; it is printed only when every phase
passed.  Without a TPU, or outside a checkout of this repository, the
script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

TRAIN_ARCH = "mamba2-370m"
TRAIN_SEQ = 512
TRAIN_STEPS = 6
DROP_EDGE, DROP_STEP = 1, 3
TRAIN_LR = 1e-3

SERVE_ARCH = "starcoder2-3b"
# 20 of the 30 layers, sized when serving held f32 weights: the full
# depth then needed 17.2 GB for prefill (15.75 GB on a v5e), 20 layers
# 12.8 GB.  A serve-only session holds bf16 weights (~6.2 GB at 30
# layers); the depth stays so the smoke's numbers remain comparable
SERVE_LAYERS = 20
SERVE_BATCH, SERVE_PROMPT, SERVE_GEN = 4, 128, 32

COMBINE_K, COMBINE_BLOCK, COMBINE_F = 2, 64, 1024 * 2048

# per-step loss agreement of the mesh modes with --dist off: both
# compute in bf16 (8-bit mantissa, relative rounding 2^-8 = 0.4%); the
# reductions run in a different order, and AdamW turns small gradient
# differences into updates of up to lr per weight, so the losses drift
# apart over the steps by a few times the rounding — 2% flags a wrong
# decode (a dropped or doubled worker moves the loss by far more)
LOSS_RTOL = 2e-2
# serving: decode logits vs the full forward pass, both bf16 over 20
# layers; a wrong mask or ring-slot position gives an O(1) error
LOGITS_REL_L2 = 5e-2


def observe(msg: str) -> None:
    print(f"[observe] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------
def train(cfg, mode: str = "off", grad_compression: str = "",
          seq_len: int = TRAIN_SEQ, steps: int = TRAIN_STEPS):
    """Coded training through CodedSession.fit; returns (losses, session)."""
    import numpy as np

    from repro.api import CodedCluster, CodedSession, planner_for_scheme

    session = CodedSession(
        CodedCluster.homogeneous(2, 2), cfg,
        planner=planner_for_scheme("hgc", 1, 1), scheme="hgc",
        mode=mode, grad_compression=grad_compression,
        seq_len=seq_len, total_steps=steps, lr=TRAIN_LR, seed=0,
        log_every=1,
    )
    rows = session.code.load * sum(session.cluster.topo.m)
    check(rows == 16, f"expected 16 coded rows, got {rows}")
    t0 = time.perf_counter()
    session.fit(1)  # compiles
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    report = session.fit(steps, force_drop_edge=DROP_EDGE,
                         force_drop_step=DROP_STEP)
    per_step = (time.perf_counter() - t0) / (steps - 1)
    losses = report["losses"]
    label = mode + (f"/{grad_compression}" if grad_compression else "")
    observe(f"train {cfg.name} {label}: {rows} rows x {seq_len} tokens, "
            f"first step (compile + run) {first:.2f} s, then "
            f"{per_step:.3f} s/step over {steps - 1} steps "
            f"(host clock, includes batch building)")
    check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    entries = session.jit_cache_entries()
    check(entries == 1, f"train step compiled {entries} times")
    return losses, session


def combine_kernels():
    """Compiled dequant-combine kernels vs the jnp references."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.dist import compression
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    g = rng.normal(size=(COMBINE_K, COMBINE_F)).astype(np.float32)
    coeff = jnp.ones((1, COMBINE_K), jnp.float32)
    refs = {"int8": ref.coded_combine_q_ref, "int4": ref.coded_combine_q4_ref,
            "fp8": ref.coded_combine_f8_ref}
    for mode, ref_fn in refs.items():
        qs, ss = zip(*(compression.quantize(g[k], block=COMBINE_BLOCK,
                                            mode=mode)[:2]
                       for k in range(COMBINE_K)))
        gq, sc = jnp.stack(qs), jnp.stack(ss)
        fn = jax.jit(lambda c, q, s, m=mode: ops.combine_compressed(
            m, c, q, s, block=COMBINE_BLOCK))
        text = fn.lower(coeff, gq, sc).compile().as_text()
        check("tpu_custom_call" in text,
              f"{mode} combine did not compile to the Pallas kernel")
        out = np.asarray(fn(coeff, gq, sc))
        want = np.asarray(ref_fn(coeff, gq, sc, COMBINE_BLOCK))
        err = float(np.max(np.abs(out - want)))
        check(err <= 1e-5 * float(np.max(np.abs(want))),
              f"{mode} combine differs from its reference by {err}")
        observe(f"combine {mode}: K={COMBINE_K} F={COMBINE_F} "
                f"block={COMBINE_BLOCK} max |kernel - ref| {err:.3g}")


def serve(cfg, batch: int = SERVE_BATCH, prompt_len: int = SERVE_PROMPT,
          gen: int = SERVE_GEN):
    """CodedSession.generate, plus the decode kernel and logits checks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import CodedSession
    from repro.api import serving
    from repro.models import transformer as tf

    session = CodedSession(None, cfg, seed=0)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (batch, prompt_len), 0, cfg.vocab)
    t0 = time.perf_counter()
    toks = session.generate(prompts, gen)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = session.generate(prompts, gen)
    steady = time.perf_counter() - t0
    observe(f"serve {cfg.name} ({cfg.n_layers} layers): {batch} x "
            f"{prompt_len}-token prompts, {toks.size} tokens generated; "
            f"first generate (compile + run) {first:.2f} s, second "
            f"{steady:.3f} s (host clock)")
    check(toks.shape == (batch, gen), f"tokens shape {toks.shape}")
    check(bool(np.all((toks >= 0) & (toks < cfg.vocab))), "token id range")
    check(bool(np.array_equal(toks, again)), "greedy decode not repeatable")

    # one decode step through the cache vs the full forward pass
    max_len = prompt_len + gen + 1
    prefill = jax.jit(serving.make_prefill_fn(cfg, max_len))
    decode = jax.jit(serving.make_decode_fn(cfg))
    _, cache = prefill(session.params, prompts)
    tok = jnp.asarray(toks[:, :1])
    compiled = decode.lower(session.params, tok, cache).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "decode step did not compile to the Pallas decode kernel")
    got, _ = decode(session.params, tok, cache)
    full = jax.jit(
        lambda p, t: tf.forward(p, cfg, t, last_only=True)[0][:, -1])
    want = full(session.params, jnp.concatenate([prompts, tok], axis=1))
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    observe(f"serve {cfg.name}: decode-step logits vs full forward, "
            f"relative L2 error {rel:.3g}")
    check(rel < LOGITS_REL_L2,
          f"decode logits differ from the full forward by {rel:.3g}")


def four_chip(cfg):
    """--dist coded / coded_q int8 on the 2x2 mesh vs --dist off."""
    import jax
    import numpy as np

    devices = jax.devices()
    ref_losses, session = train(cfg, mode="off")
    del session
    gc.collect()
    for mode, comp in (("coded", ""), ("coded_q", "int8")):
        losses, session = train(cfg, mode=mode, grad_compression=comp)
        held = {d: 0 for d in devices}
        for leaf in jax.tree.leaves((session.params, session.opt_state)):
            for shard in leaf.addressable_shards:
                held[shard.device] += shard.data.nbytes
        per_device = [held[d] for d in devices]
        observe(f"{mode}: train-state bytes per device {per_device}")
        check(min(per_device) > 0.5 * max(per_device),
              f"{mode}: train state is not spread over the devices: "
              f"{per_device}")
        diff = np.abs(np.asarray(losses) - np.asarray(ref_losses))
        rel = diff / np.abs(np.asarray(ref_losses))
        observe(f"{mode}{'/' + comp if comp else ''} vs off: losses "
                f"{[round(x, 5) for x in losses]} vs "
                f"{[round(x, 5) for x in ref_losses]}, max relative "
                f"difference {float(rel.max()):.3g} (bound {LOSS_RTOL})")
        check(float(rel.max()) <= LOSS_RTOL,
              f"{mode} losses differ from --dist off by {rel.max():.3g}")
        del session
        gc.collect()


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (pod=2, data=2) mesh phase on "
                         "four chips")
    args = ap.parse_args(argv)

    if not (SRC / "repro" / "api" / "session.py").is_file():
        sys.exit(f"chip_smoke: the program is not beside this script "
                 f"({SRC} holds no repro package)")
    sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (platform "
                 f"{dev.platform!r}); nothing runs on the CPU")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        sys.exit(f"chip_smoke: needs {need} TPU devices, found "
                 f"{len(devices)}")

    from repro.compile_cache import enable_compile_cache
    from repro.configs.registry import get_config

    observe(f"device {dev.device_kind} x {len(devices)}, jax "
            f"{jax.__version__}, compile cache {enable_compile_cache()}")
    train_cfg = get_config(TRAIN_ARCH)
    if args.four_chips:
        four_chip(train_cfg)
    else:
        losses, session = train(train_cfg)
        observe(f"train losses {losses} (ln V = "
                f"{math.log(train_cfg.vocab):.3f})")
        del session
        gc.collect()
        combine_kernels()
        serve(dataclasses.replace(get_config(SERVE_ARCH),
                                  n_layers=SERVE_LAYERS))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
