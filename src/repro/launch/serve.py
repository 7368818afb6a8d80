"""Batched serving launcher: continuous-batch decode against a KV cache.

``python -m repro.launch.serve --arch gemma-2b --smoke --requests 8``

Maintains a fixed decode batch; finished requests (EOS or length) are
replaced from the queue — a miniature continuous-batching loop over
``serve_step``, the same function the decode dry-run shapes lower.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.models import model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=configs.list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    if cfg.family == "audio":
        print("enc-dec serving: decoder-side continuous batching with a "
              "fixed encoder memory per request (stub embeddings)")
    params = model.init(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(0)
    queue = [rng.integers(0, cfg.vocab, rng.integers(4, 12)).tolist()
             for _ in range(args.requests)]
    B = args.batch

    cache = model.init_cache(cfg, B, args.max_len)
    serve = jax.jit(lambda p, b, c, l: model.serve_step(p, cfg, b, c, l))

    # slot state
    active = [None] * B  # (request_id, remaining_prompt, generated)
    next_req = 0
    done = 0
    lens = np.zeros(B, np.int64)
    t0 = time.time()
    steps = 0
    tokens_out = 0
    # NOTE: per-slot cache_len differs; for simplicity this demo advances a
    # shared position (prompts are left-aligned and padded by generation).
    pos = 0
    cur = np.zeros((B, 1), np.int32)
    while done < args.requests and pos < args.max_len - 1:
        for s in range(B):
            if active[s] is None and next_req < len(queue):
                active[s] = [next_req, list(queue[next_req]), 0]
                next_req += 1
        batch = {"tokens": jnp.asarray(cur)}
        if cfg.rope_type == "mrope":
            batch["positions"] = jnp.full((B, 3, 1), pos, jnp.int32)
        if cfg.family == "audio":
            se = min(cfg.encdec.encoder_seq, 32)
            batch["enc_embeddings"] = jnp.zeros((B, se, cfg.d_model))
            batch["enc_mask"] = jnp.ones((B, se), bool)
        logits, cache = serve(params, batch, cache, jnp.int32(pos))
        from repro.models.sampling import sample_logits
        nxt = np.asarray(sample_logits(
            jax.random.PRNGKey(pos), logits[:, -1],
            temperature=args.temperature, top_k=args.top_k), np.int32)
        for s in range(B):
            if active[s] is None:
                continue
            rid, prompt, gen = active[s]
            if prompt:
                cur[s, 0] = prompt.pop(0)  # teacher-force remaining prompt
            else:
                cur[s, 0] = nxt[s]
                active[s][2] += 1
                tokens_out += 1
                if active[s][2] >= args.max_new:
                    done += 1
                    active[s] = None
        pos += 1
        steps += 1
    dt = time.time() - t0
    print(f"served {done}/{args.requests} requests, {tokens_out} tokens in "
          f"{steps} steps, {dt:.1f}s ({tokens_out/max(dt,1e-9):.1f} tok/s "
          f"on CPU-interpret scale)")


if __name__ == "__main__":
    main()
