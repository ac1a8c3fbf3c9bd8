"""Serving driver: batched decode with a KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch <id> --smoke \
        --batch 4 --prompt-len 16 --gen 32

Prefill + decode loop with continuous batching slots: finished sequences
(EOS or length) free their slot, pending requests claim it at the next
step — the serving analogue of the dynamic engine's scheduler (vertices
enter/leave T).  Greedy sampling.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch
from repro.dist.sharding import SERVE_RULES
from repro.launch.compile_cache import use_compile_cache


def serve_lm(cfg, batch: int, prompt_len: int, gen: int,
             n_requests: int = 8, seed: int = 0):
    from repro.models import transformer as tf
    params = tf.init_params(cfg, jax.random.key(0))
    max_seq = prompt_len + gen
    cache = tf.init_kv_cache(cfg, batch, max_seq, dtype=jnp.float32)

    rng = np.random.default_rng(seed)
    pending = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]
    done = []

    decode = jax.jit(
        lambda p, c, t, pos: tf.decode_step(cfg, p, c, t, pos, SERVE_RULES))

    # slot state: current token + produced tokens per slot
    slots = [None] * batch  # each: {'toks': [...], 'made': int}
    t0 = time.time()
    steps = 0
    pos = 0
    cur = np.zeros((batch, 1), np.int32)
    while pending or any(s is not None for s in slots):
        # admit pending requests into free slots (continuous batching)
        for b in range(batch):
            if slots[b] is None and pending:
                req = pending.pop()
                slots[b] = {"toks": list(req), "made": 0, "fed": 0}
        # feed one token per active slot (prompt tokens first, then argmax)
        for b in range(batch):
            s = slots[b]
            cur[b, 0] = 0 if s is None else s["toks"][min(
                s["fed"], len(s["toks"]) - 1)]
        logits, cache = decode(params, cache, jnp.asarray(cur), pos)
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        for b in range(batch):
            s = slots[b]
            if s is None:
                continue
            s["fed"] += 1
            if s["fed"] >= len(s["toks"]):       # past the prompt: generate
                s["toks"].append(int(nxt[b]))
                s["made"] += 1
                if s["made"] >= gen:
                    done.append(s["toks"])
                    slots[b] = None
        pos += 1
        steps += 1
        if pos >= max_seq:  # ring exhausted for full-attn: flush remaining
            for b in range(batch):
                if slots[b] is not None:
                    done.append(slots[b]["toks"])
                    slots[b] = None
            break
    dt = time.time() - t0
    print(f"served {len(done)} requests in {steps} steps "
          f"({steps * batch / max(dt, 1e-9):.1f} tok/s batch={batch})")
    return done


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()
    spec = get_arch(args.arch)
    assert spec.kind in ("lm", "moe"), "serve is for LM archs"
    cfg = spec.smoke_config() if args.smoke else spec.full_config()
    serve_lm(cfg, args.batch, args.prompt_len, args.gen)


if __name__ == "__main__":
    main()
