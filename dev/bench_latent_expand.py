"""A layer's expansion of a segment's row of latents into the keys and values
of every head, alone on the chip at the GLM-5 cell's widths (one row, a table
of 17,408 columns of 640-lane latents, 64 heads of 192 + 64 and 256, the int8
`wkv_b` of 512 x 64 x 448, a segment of 2,048 queries): the whole table against
the columns the segment's queries can see, at every offset a segment of the
cell starts at. By hand, through the chip tool; not part of the benchmark's
command.

    python3 dev/bench_latent_expand.py [--tiny] [--offsets 0,2048,...] [--block 512]

One JSON line a reading, milliseconds a call (one layer), the median of
`--repeats` timings of 10 calls whose outputs are the two head-major arrays
[1, 64, 17408, 256] the segment's walk reads:

- `whole`: `models/transformer._latent_expand` of the whole table (XLA: three
  products and the relayout of their [t, h, j] into head-major);
- `loop`: the same bound in XLA alone, a `lax.fori_loop` with a dynamic trip
  count over blocks of 2,048 columns, each `_latent_expand` of a block written
  with `dynamic_update_slice` into zeroed buffers of the full shape;
- `kernel`: `ops/attention.latent_expand_blocks` up to
  `latent_columns_expanded`, what the segment program runs;
- `equal`: the elements of the columns seen in which `kernel` (and `loop`)
  differ from `whole`, keys and values, of how many; the largest difference.

(`--tiny`: a rehearsal on the CPU at `tiny-latent-moe-test`'s widths, Pallas in
interpret mode; its times mean nothing.)"""

from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from langstream_tpu.models import transformer as T  # noqa: E402
from langstream_tpu.models.configs import MODEL_PRESETS  # noqa: E402
from langstream_tpu.models.quant import quantize_weight  # noqa: E402
from langstream_tpu.ops import attention as A  # noqa: E402

CALLS = 10
TINY = dataclasses.replace(MODEL_PRESETS["tiny-latent-moe-test"], attention_impl="pallas")
# GLM-5's attention at its published widths (benchmark/configs/glm-5-int8-ep16-d7.json)
GLM = dataclasses.replace(
    MODEL_PRESETS["tiny-latent-moe-test"], name="glm-widths", n_heads=64, n_kv_heads=64,
    q_lora_rank=2048, kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
    v_head_dim=256, index_n_heads=32, index_head_dim=128, index_topk=2048, index_rope_dim=64,
)


def timed(fn, *args, repeats: int) -> float:
    """Milliseconds a call: CALLS calls of the jitted ``fn``, one launched
    ahead of the one awaited (the device is never left waiting, and no more
    than two calls' outputs of 1.14 GB are alive), the median of ``repeats``
    such timings after one warm-up."""
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        out = fn(*args)
        for _ in range(CALLS - 1):
            ahead = fn(*args)
            jax.block_until_ready(out)
            out = ahead
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t) * 1e3 / CALLS)
        del out, ahead
    return statistics.median(times)


def layer_weights(config):
    """One layer's ``wkv_b`` as the engine holds it: int8 with its scales."""
    kl, out = config.kv_lora_rank, config.qk_nope_head_dim + config.v_head_dim
    w = jax.random.normal(jax.random.PRNGKey(1), (kl, config.n_heads * out), jnp.float32)
    return {"wkv_b": quantize_weight(w * kl**-0.5)}


def loop_expand(rows, lp, seen, config, block):
    """`_latent_expand` of the blocks up to ``seen`` columns, in XLA alone."""
    b, t, _ = rows.shape
    h, d = config.n_heads, config.resolved_head_dim
    zeros = jnp.zeros((b, h, t, d), rows.dtype), jnp.zeros((b, h, t, config.v_head_dim), rows.dtype)

    def body(i, kv):
        start = jnp.minimum(i * block, t - block)  # the last block lies back on the table
        k, v = T._latent_expand(lax.dynamic_slice_in_dim(rows, start, block, axis=1), lp, config)
        return (lax.dynamic_update_slice_in_dim(kv[0], k, start, axis=2),
                lax.dynamic_update_slice_in_dim(kv[1], v, start, axis=2))

    return lax.fori_loop(0, (seen[0] + block - 1) // block, body, zeros)


def main(tiny: bool, repeats: int, offsets: list[int], block: int) -> int:
    on_chip = jax.default_backend() == "tpu"
    if not (tiny or on_chip):
        print("no TPU here: --tiny rehearses on the CPU", file=sys.stderr)
        return 2
    if tiny:
        config, s, t, dtype = TINY, 128, 1280, jnp.float32
    else:
        config, s, t, dtype = GLM, 2048, 17408, jnp.bfloat16
    config = dataclasses.replace(config, dtype=jnp.dtype(dtype).name)
    if block:  # another block than the rule's, for the kernel and the bound alike
        A.latent_expand_block = lambda s, t, config: block
    offsets = offsets or list(range(0, t - s + 1, s))
    say = lambda **line: print(json.dumps(line), flush=True)  # noqa: E731
    lp = layer_weights(config)
    assert lp["wkv_b"]["q"].dtype == jnp.int8
    rows = jax.random.normal(jax.random.PRNGKey(0), (1, t, config.latent_key_width), dtype)
    rows = rows.at[..., config.latent_width:].set(0)
    say(device=jax.devices()[0].device_kind, table=t, queries=s, heads=config.n_heads,
        latent=config.latent_key_width, offsets=offsets, block=A.latent_expand_block(s, t, config))

    whole = jax.jit(lambda rows, lp: T._latent_expand(rows, lp, config))
    kernel = jax.jit(lambda rows, lp, at: T._latent_expand_seen(rows, lp, at, s, config))
    loop = jax.jit(lambda rows, lp, seen: loop_expand(rows, lp, seen, config, s))
    say(read="whole", ms=timed(whole, rows, lp, repeats=repeats))
    want = whole(rows, lp)
    for offset in offsets:
        at = jnp.full((1,), offset, jnp.int32)
        seen = int(T.latent_columns_expanded(offset, s, t, config))
        here, upto = dict(offset=offset, columns=seen), jnp.full((1,), seen, jnp.int32)
        say(read="kernel", **here, ms=timed(kernel, rows, lp, at, repeats=repeats))
        say(read="loop", **here, ms=timed(loop, rows, lp, upto, repeats=repeats))
        for name, got in (("kernel", kernel(rows, lp, at)), ("loop", loop(rows, lp, upto))):
            differ = [int((a[:, :, :seen] != b[:, :, :seen]).sum()) for a, b in zip(got, want)]
            worst = max(
                float(jnp.abs(a[:, :, :seen].astype(jnp.float32) - b[:, :, :seen]).max())
                for a, b in zip(got, want)
            )
            say(read="equal", form=name, **here, differ=differ, of=int(want[0][:, :, :seen].size),
                worst=worst)
            del got
    return 0


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--offsets", default="", help="segment offsets, e.g. 0,2048,14336")
    parser.add_argument("--block", type=int, default=0,
                        help="columns a step of the kernel, in place of latent_expand_block's")
    args = parser.parse_args()
    raise SystemExit(main(
        args.tiny, args.repeats, [int(n) for n in args.offsets.split(",") if n], args.block
    ))
