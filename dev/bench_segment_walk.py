"""The segment walk alone, one layer a call, on the chip at the shapes of the
four cells whose prefill segments run `ops/attention._segment_kernel`: what a
KEY BLOCK costs in the kernel as it ships against PR 55's, which this file
carries for the comparison (`segment_kernel_pr55`: the body that read its
running maximum and sum as rows `[G, block_q]`, patched over
`ops/attention._segment_kernel`; PERF.md section 6, PR 56). By hand, through
the chip tool; not part of the benchmark's command, and no cell runs this file.

    python3 dev/bench_segment_walk.py [--tiny] [--shapes kimi,glm,keye,cmdaplus,cmdaplus-window]
        [--repeats 5]

Shapes (one row, a segment of 2,048 queries; query heads / KV heads x width):

- `kimi`: 64 / 64 x 192 (values 128), no window, no selection, over 17,408
  columns at offsets 0 .. 14,336 by 2,048 (`kimik25-ep32-d7-longdoc-drain`);
- `glm`: 64 / 64 x 256 under a selection of 2,048 a query, the same columns and
  offsets (`glm5-ep16-d7-longdoc-drain`);
- `keye`: 32 / 4 x 128 under a selection of 2,048 (`keyevl2-d12-longdoc-drain`);
- `cmdaplus`, `cmdaplus-window`: 128 / 8 x 128 over 12,544 columns at offsets
  0 .. 10,240 by 2,048, a full layer and a layer under a window of 4,096
  (`cmdaplus-ep8-d8-ragdocs-drain`).

One JSON line a (shape, offset): `key_blocks` one KV head's walk visits
(`ops/attention.segment_blocks_visited`), `ms` a call of each kernel (the
median of `--repeats` timings of a jitted loop of 10 calls, each call's offset
made to wait for the call before it, so the loop cannot hoist it),
`us_per_block` of each (a KV head's block: its query group's tile of scores),
and `against_pr55`, the largest difference between the two outputs, which has
to read 0.0 on the chip. A last line a shape sums its offsets: a document's
whole prefill.

(`--tiny`: a rehearsal on the CPU, Pallas in interpret mode; its times mean
nothing.)"""

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

from langstream_tpu.models.configs import MODEL_PRESETS  # noqa: E402
from langstream_tpu.ops import attention as A  # noqa: E402

CALLS = 10


def looped(fn):
    """A jitted loop of CALLS calls of ``fn(q, k, v, offsets, ..)`` whose
    results are summed. A call's offsets wait for the sum so far (and are what
    they were): a call that does not depend on the loop is hoisted out of it."""

    @jax.jit
    def loop(q, k, v, offsets, *rest):
        def body(i, total):
            after = offsets + jnp.isnan(total).astype(jnp.int32)
            return total + fn(q, k, v, after, *rest).astype(jnp.float32).sum()

        return lax.fori_loop(0, CALLS, body, jnp.float32(0))

    return loop


def timed(loop, *args, repeats: int) -> float:
    """Milliseconds a call inside ``loop``: the median of ``repeats`` timings
    after one warm-up (`dev/bench_paged_walk.py`'s)."""
    jax.block_until_ready(loop(*args))
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        jax.block_until_ready(loop(*args))
        times.append((time.perf_counter() - t) * 1e3 / CALLS)
    return statistics.median(times)


def segment_kernel_pr55(
    offsets_ref, q_ref, k_ref, v_ref, *refs,
    block_q: int, block_k: int, window: int, n_t: int, scale: float, softcap,
    selected: bool = False,
):
    """`ops/attention._segment_kernel` as PR 55 left it, line for line: the
    running maximum and sum read and written as rows `[G, block_q]`."""
    pl = A.pl
    chosen_ref = refs[0] if selected else None
    o_ref, m_scr, l_scr, acc_scr = refs[1:] if selected else refs
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, A._NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = offsets_ref[b] + i * block_q
    first, last = A._segment_blocks(q_start, block_q, block_k, window, n_t)
    at = first + j

    @pl.when(at <= last)
    def _body():
        q = q_ref[0, 0, :, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_q, block_k), 1)
        k_pos = at * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_q, block_k), 2)
        seen = k_pos <= q_pos
        if window:
            seen = seen & (k_pos > q_pos - window)
        if selected:
            seen = seen & (chosen_ref[...].astype(jnp.int32) != 0)
        s = jnp.where(seen, s, A._NEG)
        m_prev = m_scr[:, :, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, :, None])
        p = jnp.where(s <= A._NEG, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, :, 0] = l_scr[:, :, 0] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr[:, :, None] + pv
        m_scr[:, :, 0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :, 0], 1e-30)[:, :, None]
        o_ref[0, 0, :, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


@dataclasses.dataclass
class Shape:
    heads: int
    kv_heads: int
    width: int
    value_width: int
    columns: int
    selected: int = 0  # keys a query keeps
    window: int = 0
    segment: int = 2048


SHAPES = {
    "kimi": Shape(64, 64, 192, 128, 17408),
    "glm": Shape(64, 64, 256, 256, 17408, selected=2048),
    "keye": Shape(32, 4, 128, 128, 17408, selected=2048),
    "cmdaplus": Shape(128, 8, 128, 128, 12544),
    "cmdaplus-window": Shape(128, 8, 128, 128, 12544, window=4096),
}
TINY = {
    "kimi": Shape(4, 4, 64, 32, 1024, segment=256),
    "glm": Shape(4, 4, 64, 64, 1024, selected=256, segment=256),
    "keye": Shape(8, 2, 64, 64, 1024, selected=256, segment=256),
    "cmdaplus": Shape(8, 2, 64, 64, 1024, segment=256),
    "cmdaplus-window": Shape(8, 2, 64, 64, 1024, window=384, segment=256),
}


def build(shape: Shape, on_chip: bool):
    """(call(q, k, v, offsets[, chosen]) -> output, make_args(offset))."""
    dtype = jnp.bfloat16 if on_chip else jnp.float32
    s, t = shape.segment, shape.columns
    config = dataclasses.replace(
        MODEL_PRESETS["tiny-test"], attention_impl="pallas", n_heads=shape.heads,
        n_kv_heads=shape.kv_heads, head_dim=shape.width,
    )
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (1, s, shape.heads, shape.width), dtype)
    k = jax.random.normal(keys[1], (1, shape.kv_heads, t, shape.width), dtype)
    v = jax.random.normal(keys[2], (1, shape.kv_heads, t, shape.value_width), dtype)
    interpret = not on_chip

    if not shape.selected:
        def call(q, k, v, offsets):
            return A.flash_segment_attention(
                q, k, v, offsets, config, window=shape.window, interpret=interpret)

        return call, lambda offset: (q, k, v, jnp.asarray([offset], jnp.int32))

    score = jax.random.uniform(keys[3], (s, t))

    @jax.jit
    def choose(offset):
        """A query's ``selected`` highest of the columns up to its own."""
        visible = jnp.arange(t)[None, :] <= offset + jnp.arange(s)[:, None]
        ranked = jnp.where(visible, score, -1.0)
        kth = lax.top_k(ranked, shape.selected)[0][:, -1:]
        return (visible & (ranked >= kth)).astype(jnp.int8)[None]

    def call(q, k, v, offsets, chosen):
        return A.sparse_segment_attention(q, k, v, offsets, chosen, config, interpret=interpret)

    return call, lambda offset: (
        q, k, v, jnp.asarray([offset], jnp.int32), choose(jnp.int32(offset)))


def main(tiny: bool, repeats: int, shapes: list[str]) -> int:
    on_chip = jax.default_backend() == "tpu"
    if not (tiny or on_chip):
        print("no TPU here: --tiny rehearses on the CPU", file=sys.stderr)
        return 2
    say = lambda **line: print(json.dumps(line), flush=True)  # noqa: E731
    say(device=jax.devices()[0].device_kind, root=str(ROOT), shapes=shapes, calls=CALLS)
    own_kernel = A._segment_kernel
    for name in shapes:
        shape = (TINY if tiny else SHAPES)[name]
        call, make_args = build(shape, on_chip)
        group = shape.heads // shape.kv_heads
        itemsize = 2 if on_chip else 4
        sizes = (shape.segment, shape.columns, shape.width, group, shape.window, itemsize)
        block_q, block_k, _ = A.segment_key_blocks(*sizes)
        offsets = range(0, shape.columns - shape.segment, shape.segment)
        readings = {}
        for kernel in ("change", "pr55"):
            A._segment_kernel = own_kernel if kernel == "change" else segment_kernel_pr55
            jax.clear_caches()  # a trace is cached by the function, not by the patch
            once, loop = jax.jit(call), looped(call)  # one compile a kernel: the offset is data
            try:
                for offset in offsets:
                    args = make_args(offset)
                    readings[kernel, offset] = (timed(loop, *args, repeats=repeats), once(*args))
            finally:
                A._segment_kernel = own_kernel
        jax.clear_caches()
        total = {"key_blocks": 0, "change": 0.0, "pr55": 0.0}
        for offset in offsets:
            visited = A.segment_blocks_visited(offset, *sizes)
            (ms, out), (ms_pr55, out_pr55) = readings["change", offset], readings["pr55", offset]
            walked = visited * shape.kv_heads  # blocks a call runs its body for
            say(shape=name, offset=offset, block_q=block_q, block_k=block_k, group=group,
                key_blocks=visited, ms={"change": ms, "pr55": ms_pr55},
                us_per_block={"change": ms * 1e3 / walked, "pr55": ms_pr55 * 1e3 / walked},
                gain=1 - ms / ms_pr55,
                against_pr55=float(jnp.abs(out.astype(jnp.float32) - out_pr55).max()),
                max_abs=float(jnp.abs(out_pr55.astype(jnp.float32)).max()))
            total["key_blocks"] += visited
            total["change"] += ms
            total["pr55"] += ms_pr55
        walked = total["key_blocks"] * shape.kv_heads
        say(shape=name, offset="all", key_blocks=total["key_blocks"],
            ms={"change": total["change"], "pr55": total["pr55"]},
            us_per_block={k: total[k] * 1e3 / walked for k in ("change", "pr55")},
            gain=1 - total["change"] / total["pr55"])
    return 0


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--shapes", default=",".join(SHAPES))
    args = parser.parse_args()
    raise SystemExit(main(args.tiny, args.repeats, args.shapes.split(",")))
