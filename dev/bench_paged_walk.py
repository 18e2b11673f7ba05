"""The paged decode walk alone, one layer a call, on the chip at the shapes of
the nine cells (PR 51's, kept by PR 52): what a PAGE costs when a loop step of
`ops/attention._paged_decode_kernel` takes 1, 2, 4 or 8 of them, against the
time its bytes take at the chip's 819 GB/s. By hand, through the chip tool; not
part of the benchmark's command, and no cell runs this file.

    python3 dev/bench_paged_walk.py [--tiny] [--root <checkout>] [--groups 0,1,2,4,8]
        [--shapes kimi,glm,keye,chat,...] [--folds in-order,joint]

Shapes (rows x KV heads x query rows a head x width; pages of 64 tokens):

- `kimi`: the latent read with no mask, 16 x 1 x 64 x 640 (value 512), rows of
  205 pages in a table of 272 (`kimik25-ep32-d7-longdoc-drain`);
- `glm`: the same under a selection of 2,048 (`glm5-ep16-d7-longdoc-drain`);
- `keye`: K and V under a selection, 8 x 4 x 8 x 128, rows of 205 of 272
  (`keyevl2-d12-longdoc-drain`);
- `chat`: 64 x 8 x 4 x 128, rows of 1 to 20 pages (`mistral7b-chat-steady`);
- `docs`: the int8 pool, 16 x 8 x 4 x 128, rows of 17 to 33 pages
  (`mistral7b-docs-drain`);
- `drain`: chat's rows of 2 to 10 pages (`mixtral8x7b-d6-decode-drain`);
- `olmo`: 40 x 30 x 1 x 128, rows of 2 to 10 pages, 983 KB a page
  (`olmohybrid7b-decode-drain`);
- `sdar`: the block pass, 64 x 4 x 32 x 128, rows of 3 to 11 pages;
- `window`: 16 x 8 x 16 x 128 under a lower bound 4,096 tokens behind the
  length, rows of 190 pages (`cmdaplus-ep8-d8-ragdocs-drain`'s window layers).

One JSON line a reading: `ms` a call (the median of `--repeats` timings of a
jitted loop of 20 calls), `us_per_page` over the live pages of the batch,
`bytes_us_per_page` (a page's bytes at 819 GB/s) and `gb_per_s` of the pages
walked. `fold`: "in-order" is the kernel's own (the group's scores from one
product, the pages folded one after the other: bit-equal to a walk of single
pages); "joint" (`--folds in-order,joint`) is the alternative this file
carries for the comparison (one maximum, one exponential, one `p @ v` and one
rescale a group: other sums, ROADMAP B11 (1)), patched over
`ops/attention._fold_pages`. `against_single` is the largest difference
between each form's output and the walk of single pages, over the same rows:
0.0 for the kernel's own fold. The forced shapes hold the slots the kernel
gives that group (`_walk_slots`; `--slots-per-group` n: n x the group, if
more); the kernel's own rule is `ops/attention._walk_shape`, and `--groups 0`
reads it as it ships. `--root`: another checkout's kernel (a parent's, whose
walk takes one page whatever is asked of it, reads the same at every group).

(`--tiny`: a rehearsal on the CPU, Pallas in interpret mode; its times mean
nothing.)"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if "--root" in sys.argv:  # before the imports below: whose kernel is read
    ROOT = Path(sys.argv[sys.argv.index("--root") + 1]).resolve()
sys.path[:0] = [str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from langstream_tpu.models.configs import MODEL_PRESETS  # noqa: E402
from langstream_tpu.ops import attention as A  # noqa: E402

CALLS = 20
PAGE = 64
LAYERS = 2


def timed(fn, *args, repeats: int) -> float:
    """Milliseconds a call of ``fn(i, *args)``: a jitted loop of CALLS calls
    whose results are summed, the median of ``repeats`` timings after one
    warm-up (`dev/bench_selected_read.py`'s)."""

    @jax.jit
    def loop(*args):
        def body(i, total):
            return total + fn(i, *args).astype(jnp.float32).sum()

        return lax.fori_loop(0, CALLS, body, jnp.float32(0))

    jax.block_until_ready(loop(*args))
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        jax.block_until_ready(loop(*args))
        times.append((time.perf_counter() - t) * 1e3 / CALLS)
    return statistics.median(times)


def fold_joint(in_order, carry, s, p_scale, v, masked, page_size):
    """The alternative fold: the step's pages as ONE page of the kernel's own
    fold: one maximum, one exponential, one `p @ v` and one rescale a step."""
    whole = None if p_scale is None else [jnp.concatenate(p_scale, axis=-1)]
    return in_order(carry, s, whole, v, masked, s.shape[-1])


@dataclasses.dataclass
class Shape:
    rows: int
    kv_heads: int
    group: int  # query rows a KV head
    width: int
    table: int
    pages: tuple  # (fewest, most) pages a row
    kind: str = "kv"  # "kv" | "int8" | "latent" | "block"
    selected: bool = False
    window: int = 0  # tokens
    block: int = 1


SHAPES = {
    "kimi": Shape(16, 1, 64, 640, 272, (205, 205), kind="latent"),
    "glm": Shape(16, 1, 64, 640, 272, (205, 205), kind="latent", selected=True),
    "keye": Shape(8, 4, 8, 128, 272, (205, 205), selected=True),
    "chat": Shape(64, 8, 4, 128, 20, (1, 20)),
    "docs": Shape(16, 8, 4, 128, 33, (17, 33), kind="int8"),
    "drain": Shape(64, 8, 4, 128, 10, (2, 10)),
    "olmo": Shape(40, 30, 1, 128, 10, (2, 10)),
    "sdar": Shape(64, 4, 8, 128, 11, (3, 11), kind="block", block=4),
    "window": Shape(16, 8, 16, 128, 196, (190, 190), window=4096),
}


def build(shape: Shape, tiny: bool, on_chip: bool):
    """(call(i, *args) -> output, args, live pages of the batch, bytes walked)."""
    page = 8 if tiny else PAGE
    rows, table = (min(shape.rows, 4), min(shape.table, 12)) if tiny else (shape.rows, shape.table)
    width = 128 if tiny and shape.kind == "latent" else shape.width
    lo, hi = (min(n, table) for n in shape.pages)
    dtype = jnp.bfloat16 if on_chip else jnp.float32
    rng = np.random.default_rng(0)
    per_row = rng.integers(lo, hi + 1, rows)
    # a row ends inside its last page
    lengths = jnp.asarray(per_row * page - rng.integers(0, page, rows), jnp.int32)
    pool_pages = rows * table
    tables = jnp.asarray(rng.permutation(pool_pages).reshape(rows, table), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    interpret = not on_chip
    t = table * page
    window = min(shape.window, 2 * page) if tiny else shape.window
    visible = jnp.arange(t)[None, :] < lengths[:, None]
    chosen = None
    if shape.selected:
        topk = 2 * page if tiny else 2048
        score = jnp.where(visible, jax.random.uniform(keys[3], (rows, t)), -1.0)
        kth = lax.top_k(score, min(topk, t))[0][:, -1:]
        chosen = visible & (score >= kth)
    if shape.kind == "latent":
        base = MODEL_PRESETS["tiny-latent-dense-moe-test"]
        rank = 64 if tiny else 512
        config = dataclasses.replace(
            base, attention_impl="pallas", n_heads=shape.group, n_kv_heads=shape.group,
            kv_lora_rank=rank,
        )
        q = jax.random.normal(keys[0], (rows, shape.group, width), dtype)
        lat = jax.random.normal(keys[1], (LAYERS, pool_pages, 1, page, width), dtype)
        per_page = page * width * lat.dtype.itemsize

        def call(i, q, lat, lengths, tables, chosen):
            return A.ragged_paged_latent_attention(
                q, lat, lengths, tables, i % LAYERS, chosen, config, page, interpret=interpret)

        args = (q, lat, lengths, tables, chosen)
    else:
        config = dataclasses.replace(
            MODEL_PRESETS["tiny-test"], attention_impl="pallas",
            n_heads=shape.kv_heads * shape.group, n_kv_heads=shape.kv_heads, head_dim=width,
        )
        heads = shape.kv_heads * shape.group
        pool = (LAYERS, pool_pages, shape.kv_heads, page, width)
        pk, pv = (jax.random.normal(k, pool, dtype) for k in keys[1:3])
        per_page = 2 * shape.kv_heads * page * width * pk.dtype.itemsize
        if shape.kind == "int8":
            pk, pv = (
                {"q": (leaf * 40).astype(jnp.int8), "s": jnp.full(pool[:-1], 0.02, jnp.float32)}
                for leaf in (pk, pv)
            )
            per_page //= 2
            q = jax.random.normal(keys[0], (rows, heads, width), dtype)

            def call(i, q, pk, pv, lengths, tables):
                return A.ragged_paged_decode_attention_int8(
                    q, pk, pv, lengths, tables, i % LAYERS, config, page, interpret=interpret)

            args = (q, pk, pv, lengths, tables)
        elif shape.kind == "block":
            q = jax.random.normal(keys[0], (rows, shape.block, heads, width), dtype)

            def call(i, q, pk, pv, lengths, tables):
                return A.ragged_paged_block_attention(
                    q, pk, pv, lengths, tables, i % LAYERS, config, page, interpret=interpret)

            args = (q, pk, pv, lengths, tables)
        elif shape.selected:
            q = jax.random.normal(keys[0], (rows, heads, width), dtype)

            def call(i, q, pk, pv, lengths, tables, chosen):
                return A.ragged_paged_selected_attention(
                    q, pk, pv, lengths, tables, i % LAYERS, chosen, config, page,
                    interpret=interpret)

            args = (q, pk, pv, lengths, tables, chosen)
        else:
            q = jax.random.normal(keys[0], (rows, heads, width), dtype)
            lower = jnp.maximum(lengths - window, 0) if window else None

            def call(i, q, pk, pv, lengths, tables, lower):
                return A.ragged_paged_decode_attention(
                    q, pk, pv, lengths, tables, i % LAYERS, config, page, interpret=interpret,
                    lower=lower)

            args = (q, pk, pv, lengths, tables, lower)
    first = (jnp.maximum(lengths - window, 0) // page) if window else 0
    live = int((-(-lengths // page) - first).sum())
    return call, args, live, live * per_page


def main(
    tiny: bool, repeats: int, groups: list[int], shapes: list[str], slots_per_group: int,
    folds: list[str],
) -> int:
    on_chip = jax.default_backend() == "tpu"
    if not (tiny or on_chip):
        print("no TPU here: --tiny rehearses on the CPU", file=sys.stderr)
        return 2
    say = lambda **line: print(json.dumps(line), flush=True)  # noqa: E731
    say(device=jax.devices()[0].device_kind, root=str(ROOT), groups=groups, shapes=shapes,
        slots_per_group=slots_per_group)
    own_fold, own_shape = A._fold_pages, A._walk_shape
    for name in shapes:
        call, args, live, walked = build(SHAPES[name], tiny, on_chip)
        single = None
        sizes = dict(  # a page of every leaf, and the time its bytes take at 819 GB/s
            live_pages=live, page_kb=walked / live / 1024,
            bytes_us_per_page=walked / live / 819e3,
        )

        def noted():  # what the trace just made wrote under `attention_paths()`
            return {k: v for k, v in A.attention_paths().items() if k.startswith("paged-walk[")}

        for group in groups:
            if group == 0:  # the kernel's own rule, as it ships
                jax.clear_caches()
                A._PATHS.clear()
                ms = timed(call, *args, repeats=repeats)
                say(shape=name, group="own", fold="in-order", **sizes, ms=ms,
                    us_per_page=ms * 1e3 / live, gb_per_s=walked / ms / 1e6, walk=noted())
                continue
            for fold in folds if group > 1 else folds[:1]:
                A._walk_shape = lambda *a, n=group: (n, max(slots_per_group * n, A._walk_slots(n)))
                A._fold_pages = (
                    own_fold if fold == "in-order" else functools.partial(fold_joint, own_fold)
                )
                jax.clear_caches()  # a trace is cached by the function, not by these
                try:
                    out = jax.jit(call)(jnp.int32(1), *args).astype(jnp.float32)
                    ms = timed(call, *args, repeats=repeats)
                except Exception as e:  # what Mosaic refuses at this shape
                    say(shape=name, group=group, fold=fold, error=str(e).splitlines()[0][:300])
                    continue
                finally:
                    A._walk_shape, A._fold_pages = own_shape, own_fold
                single = out if single is None else single
                say(shape=name, group=group, fold=fold, **sizes, ms=ms,
                    us_per_page=ms * 1e3 / live, gb_per_s=walked / ms / 1e6,
                    against_single=float(jnp.abs(out - single).max()),
                    max_abs=float(jnp.abs(single).max()))
    return 0


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--groups", default="0,1,2,4,8")
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--slots-per-group", type=int, default=0)
    parser.add_argument("--folds", default="in-order")
    parser.add_argument("--root", default=str(ROOT))  # read above, before the imports
    args = parser.parse_args()
    raise SystemExit(main(
        args.tiny, args.repeats, [int(n) for n in args.groups.split(",")],
        args.shapes.split(","), args.slots_per_group, args.folds.split(","),
    ))
