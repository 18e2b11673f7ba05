"""Experiment: does the in-place layer scan kill the decode-scan cache
double-buffer (VERDICT r4 weak #4)?

Compares the current chunk form (decode_step: layer scan consumes cache as
xs, stacks fresh ys) against decode_step_inplace (carry + DUS) inside the
same steps-scan, reporting peak HBM and step time per batch size.

Usage: python dev/exp_decode_buffer.py [--preset llama-3-8b] [--batches 48,64,80]
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def mem_stats():
    d = jax.devices()[0]
    try:
        s = d.memory_stats()
        return s.get("peak_bytes_in_use", 0), s.get("bytes_in_use", 0)
    except Exception:
        return 0, 0


def make_chunk_fn(body_step, config, steps, kv_bound=None):
    from langstream_tpu.serving.sampling import sample

    @functools.partial(jax.jit, donate_argnames=("cache",))
    def chunk(params, tokens, positions, cache, key, temp, top_k, top_p):
        def body(carry, _):
            tokens, positions, cache, key = carry
            logits, cache = body_step(params, tokens, positions, cache, config, kv_bound=kv_bound)
            key, sub = jax.random.split(key)
            nxt = sample(logits, sub, temp, top_k, top_p)
            return (nxt, positions + 1, cache, key), nxt

        (tokens, positions, cache, key), out = lax.scan(
            body, (tokens, positions, cache, key), None, length=steps
        )
        return out, tokens, positions, cache, key

    return chunk


def run(preset: str, batch: int, steps: int, variant: str, seq_len: int) -> None:
    from langstream_tpu.models.configs import MODEL_PRESETS
    from langstream_tpu.models.quant import init_random_quantized_params
    from langstream_tpu.models.transformer import (
        decode_step,
        decode_step_inplace,
        make_kv_cache,
    )

    config = MODEL_PRESETS[preset]
    config = dataclasses.replace(
        config, kv_cache_dtype="int8", attention_impl=args.attn_impl
    )
    params = init_random_quantized_params(config, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    base_peak, base_now = mem_stats()

    cache = make_kv_cache(config, batch, seq_len)
    tokens = jnp.ones(batch, jnp.int32)
    positions = jnp.full(batch, args.positions, jnp.int32)
    key = jax.random.PRNGKey(0)
    temp = jnp.zeros(batch, jnp.float32)
    top_k = jnp.zeros(batch, jnp.int32)
    top_p = jnp.ones(batch, jnp.float32)

    step = decode_step_inplace if variant == "inplace" else (
        lambda p, t, po, c, cf, kv_bound=None: decode_step(p, t, po, c, cf)
    )
    fn = make_chunk_fn(step, config, steps, kv_bound=args.kv_bound)

    t0 = time.monotonic()
    out, tokens, positions, cache, key = fn(
        params, tokens, positions, cache, key, temp, top_k, top_p
    )
    first = float(np.asarray(jax.device_get(out[-1, 0])))
    compile_s = time.monotonic() - t0

    # timed: 3 chained chunks, forced fetch at the end
    n_chunks = 3
    t0 = time.monotonic()
    for _ in range(n_chunks):
        out, tokens, positions, cache, key = fn(
            params, tokens, positions, cache, key, temp, top_k, top_p
        )
    _ = float(np.asarray(jax.device_get(out[-1, 0])))
    dt = time.monotonic() - t0
    peak, now = mem_stats()
    toks = batch * steps * n_chunks
    print(
        f"RESULT variant={variant} preset={preset} B={batch} steps={steps} "
        f"compile={compile_s:.1f}s time={dt*1e3:.0f}ms tok/s={toks/dt:.0f} "
        f"ms/step={dt*1e3/(steps*n_chunks):.2f} "
        f"peak_gib={peak/2**30:.2f} now_gib={now/2**30:.2f} "
        f"base_now_gib={base_now/2**30:.2f} (first_tok={first})",
        flush=True,
    )


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="llama-3-8b")
    p.add_argument("--batches", default="48")
    p.add_argument("--steps", type=int, default=16)
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--variant", default="inplace", choices=["inplace", "scan", "both"])
    p.add_argument("--kv-bound", type=int, default=None)
    p.add_argument("--attn-impl", default="auto")
    p.add_argument("--positions", type=int, default=32)
    args = p.parse_args()
    variants = ["scan", "inplace"] if args.variant == "both" else [args.variant]
    for b in [int(x) for x in args.batches.split(",")]:
        for v in variants:
            try:
                run(args.preset, b, args.steps, v, args.seq_len)
            except Exception as e:  # noqa: BLE001
                print(f"RESULT variant={v} B={b} FAILED: {type(e).__name__}: {str(e)[:200]}", flush=True)
                sys.exit(0)  # OOM poisons the runtime; bail and rerun per-B
