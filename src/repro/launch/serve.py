"""Batched serving driver CLI: prefill + decode with KV caches.

Front-end over :meth:`repro.api.CodedSession.generate`: the session
owns the compiled prefill/decode steps — the prompt is prefetched
through the bulk ``tf.prefill`` lowering (one dispatch, handed off into
the decode ring buffers) instead of the old S-step ``decode_step``
loop, and ``--tp N`` shards both steps tensor-parallel across N host
devices from the same pspec rules training uses.

``--exact-handoff`` keeps the token-by-token prefill as a debug path
(it is also the automatic fallback for recurrent / encoder-decoder
archs whose states only exist on the decode path).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --batch 4 --prompt-len 16 --gen 32
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --batch 4 --gen 32 --tp 2
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.api import CodedSession
from repro.api.serving import generate, prefill_into_cache  # noqa: F401
from repro.compile_cache import enable_compile_cache
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.models import transformer as tf


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard the prefill/"
                         "decode steps over a 'model' mesh axis of N "
                         "host devices (1 = single host)")
    ap.add_argument("--exact-handoff", action="store_true",
                    help="debug: feed the prompt through decode_step "
                         "token by token instead of the bulk prefill")
    ap.add_argument("--f32", action="store_true",
                    help="force float32 compute: bf16 rounding depends "
                         "on the shard layout, f32 makes greedy tokens "
                         "invariant to the TP degree")
    ap.add_argument("--tokens-out", default="",
                    help="write the generated token matrix as JSON")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    if args.f32:
        import dataclasses

        cfg = dataclasses.replace(cfg, dtype="float32")
    session = CodedSession(None, cfg, tp=args.tp, seed=args.seed)
    rng = jax.random.PRNGKey(args.seed)
    prompt = jax.random.randint(
        rng, (args.batch, args.prompt_len), 0, cfg.vocab
    )
    enc = None
    if cfg.is_encdec:
        enc = jax.random.normal(
            rng, (args.batch, cfg.enc_len, cfg.d_model)
        )
    t0 = time.time()
    toks = session.generate(
        prompt, args.gen,
        max_len=args.prompt_len + args.gen + 1, enc_frames=enc,
        seed=args.seed, exact_handoff=args.exact_handoff,
    )
    dt = time.time() - t0
    mode = "exact-handoff" if (args.exact_handoff
                               or not tf.bulk_prefill_supported(cfg)) \
        else "bulk-prefill"
    print(f"[serve] {args.arch} (tp={args.tp}, {mode}): generated "
          f"{toks.shape} tokens in {dt:.1f}s "
          f"({args.batch*args.gen/dt:.1f} tok/s)")
    print("[serve] sample:", toks[0][:16].tolist())
    if args.tokens_out:
        with open(args.tokens_out, "w") as f:
            json.dump({"tp": args.tp, "tokens": toks.tolist()}, f)
    return toks


if __name__ == "__main__":
    main()
