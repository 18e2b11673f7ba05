"""Measure the two candidate shapes of a fused prefill–decode iteration:

  A. back-to-back async dispatches — the admission/segment program followed
     immediately by the decode-chunk program (what the engine ships): two
     dispatch overheads per iteration, ZERO new compiled programs (both
     halves are already in the warmed set).
  B. single fused program — one jit tracing the SAME two halves (the
     prefill segment forward and the decode-chunk scan) as one XLA
     program: one dispatch, but a NEW program per (steps, kv_bound,
     segment width) combination — i.e. the warm set multiplies
     {ladder} × {buckets}, and every novel combo is one more compile
     on the chip. (A deeper fusion — prefill and decode
     ROWS sharing one attention call — would build on
     ops.attention.fused_segment_decode_attention, exactness-tested but
     not used here.)

On an in-order device stream both shapes execute the same work in the same
order; the measurable difference is per-iteration dispatch overhead vs the
compile-surface multiplication. Run on the target chip to confirm the PERF.md round-6
decision; on CPU it reports the dispatch-overhead delta only.

Usage: python dev/exp_fused_iteration.py [iters]
"""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from langstream_tpu.models.configs import MODEL_PRESETS
    from langstream_tpu.models.transformer import (
        init_params,
        make_kv_cache,
        prefill_segment,
    )
    from langstream_tpu.serving.engine import _decode_chunk

    iters = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    config = MODEL_PRESETS["gemma-2b" if on_tpu else "tiny-test"]
    b, t, w, steps = (96, 512, 64, 16) if on_tpu else (4, 128, 32, 4)
    params = init_params(config, jax.random.PRNGKey(0))
    cache = make_kv_cache(config, b, t)
    local = make_kv_cache(config, 1, w)
    tokens = jnp.ones(b, jnp.int32)
    positions = jnp.full(b, 40, jnp.int32)
    temp = jnp.zeros(b, jnp.float32)
    top_k = jnp.zeros(b, jnp.int32)
    top_p = jnp.ones(b, jnp.float32)
    seg = jnp.ones((1, w), jnp.int32)
    key = jax.random.PRNGKey(1)
    kv_bound = 64

    def back_to_back(cache, local, key):
        # dispatch 1: one prefill segment (stands in for admit_group too)
        _, local = prefill_segment(
            params, seg, jnp.zeros(1, jnp.int32), jnp.full(1, w, jnp.int32),
            local, config,
        )
        # dispatch 2: the decode chunk — queued behind dispatch 1 on the
        # in-order stream without any host sync between them
        chunk, *_, cache, key = _decode_chunk(
            params, tokens, positions, cache, key, temp, top_k, top_p,
            steps, config, kv_bound,
        )
        return cache, local, key, chunk

    fused_one = jax.jit(
        lambda cache, local, key: back_to_back(cache, local, key),
        donate_argnums=(0, 1),
    )

    for name, fn in (("back-to-back", back_to_back), ("single-program", fused_one)):
        c = make_kv_cache(config, b, t)
        l = make_kv_cache(config, 1, w)
        k = jax.random.PRNGKey(1)
        c, l, k, chunk = fn(c, l, k)  # compile
        jax.block_until_ready(chunk)
        t0 = time.monotonic()
        for _ in range(iters):
            c, l, k, chunk = fn(c, l, k)
        jax.block_until_ready(chunk)
        dt = (time.monotonic() - t0) / iters
        print(f"{name}: {dt * 1e3:.2f} ms/iteration")


if __name__ == "__main__":
    main()
