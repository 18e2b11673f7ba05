"""Profile the chunked-prefill (long-context) path segment by segment.

Replays exactly what engine._long_step dispatches for a 32k llama-3.1-8b
prompt (int8 weights + int8 KV): 16 segments of 2048 through
_prefill_segment_and_sample with the pow2 kv_bound ladder. Prints
per-segment wall time (warm, forced fetch) and the attention kernel's
share, so the 32k TTFT can be attributed (19.0s against a ~4-6s roofline
is a figure from a deleted chip record — a claim to check).
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="llama-3.1-8b")
    p.add_argument("--prompt-len", type=int, default=32000)
    p.add_argument("--segment", type=int, default=2048)
    p.add_argument("--max-seq", type=int, default=32768)
    p.add_argument("--attn-only", action="store_true")
    args = p.parse_args()

    from langstream_tpu.models.configs import MODEL_PRESETS
    from langstream_tpu.models.quant import init_random_quantized_params
    from langstream_tpu.models.transformer import make_kv_cache
    from langstream_tpu.serving.engine import _prefill_segment_and_sample

    config = MODEL_PRESETS[args.preset]
    config = dataclasses.replace(config, kv_cache_dtype="int8")
    params = init_random_quantized_params(config, jax.random.PRNGKey(0))
    jax.block_until_ready(params)

    width = args.segment
    prompt_len = args.prompt_len
    t_long = width
    while t_long < prompt_len:
        t_long *= 2
    t_long = min(t_long, args.max_seq)

    if args.attn_only:
        attn_only(config, width, t_long)
        return

    rng = np.random.default_rng(0)
    n_seg = -(-prompt_len // width)

    def run_pass(label: str) -> None:
        cache = make_kv_cache(config, 1, t_long)
        key = jax.random.PRNGKey(0)
        total = 0.0
        for seg in range(n_seg):
            s0 = seg * width
            seg_len = min(width, prompt_len - s0)
            kv_bound = width
            while kv_bound < min(s0 + width, t_long):
                kv_bound *= 2
            kv_bound = min(kv_bound, t_long)
            tokens = rng.integers(1, config.vocab_size, size=(1, width)).astype(np.int32)
            t0 = time.monotonic()
            first, cache, key = _prefill_segment_and_sample(
                params, jnp.asarray(tokens), jnp.asarray([s0], jnp.int32),
                jnp.asarray([seg_len], jnp.int32), cache, key,
                jnp.asarray([0.0], jnp.float32), jnp.asarray([0], jnp.int32),
                jnp.asarray([1.0], jnp.float32), config, kv_bound,
            )
            _ = np.asarray(jax.device_get(first))  # force completion
            dt = time.monotonic() - t0
            total += dt
            print(
                f"  [{label}] seg {seg:2d} s0={s0:6d} kv_bound={kv_bound:6d}: "
                f"{dt*1e3:7.1f}ms",
                flush=True,
            )
        print(f"[{label}] total={total:.2f}s over {n_seg} segments", flush=True)

    run_pass("cold")  # includes compiles
    run_pass("warm")


def attn_only(config, width: int, t_long: int) -> None:
    """Time flash_segment_attention alone at a late-segment shape."""
    from langstream_tpu.ops.attention import flash_segment_attention

    b, h, hkv, d = 1, config.n_heads, config.n_kv_heads, config.resolved_head_dim
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, width, h, d), jnp.bfloat16)
    k = jax.random.normal(key, (b, hkv, t_long, d), jnp.bfloat16)
    v = jax.random.normal(key, (b, hkv, t_long, d), jnp.bfloat16)
    offset = jnp.asarray([t_long - width], jnp.int32)

    import os

    bq = int(os.environ.get("BQ", "512"))
    bk = int(os.environ.get("BK", "512"))
    fn = jax.jit(
        lambda q, k, v, o: flash_segment_attention(
            q, k, v, o, config, block_q=bq, block_k=bk
        )
    )
    out = fn(q, k, v, offset)
    _ = np.asarray(jax.device_get(out[0, 0, :4]))
    n = 5
    t0 = time.monotonic()
    for _ in range(n):
        out = fn(q, k, v, offset)
    _ = np.asarray(jax.device_get(out[0, 0, :4]))
    dt = (time.monotonic() - t0) / n
    flops = 2 * 2 * width * (t_long - width // 2) * h * d  # QK + PV, causal avg
    print(
        f"attn-only width={width} t={t_long}: {dt*1e3:.1f}ms "
        f"≈{flops/dt/1e12:.1f} TFLOPS effective",
        flush=True,
    )


if __name__ == "__main__":
    sys.exit(main())
