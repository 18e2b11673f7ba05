"""A prefill segment's write by whole pages against the scatter, on the chip at
the three segment cells' widths (`tests/test_tpu_compile.py`'s KEYE, CMDA and
GLM, fewer experts held so that a bf16 tree fits before it is quantized). By
hand, through the chip tool; not part of the benchmark's command.

    python3 dev/check_segment_write.py [--tiny] [--models keye,cmda,glm] [--segments 3]

Two readings a model, one JSON line each:

- `writer`: `models/transformer._paged_write_rows` ALONE, one layer's new rows
  of a 2,048-token segment into pools of the cell's leaves, by whole pages and
  by the scatter from the same operands: `equal` (every leaf's bits, asserted)
  and the milliseconds a layer's call of each (the median of `--repeats` timings
  of a jitted loop of 10 calls over a donated pool).
- `program`: `paged_prefill_segment_inplace` WHOLE, `--segments` segments of one
  row one after another from one seeded pool and tree, once as the program
  lowers (the page writer) and once held to the scatter (`_copies_pages` says
  no: the parent's program): after each segment whether each leaf of the two
  pools is the same to the bit, else LAYER by layer how many of its values
  differ and by how much at most, and the largest difference of the two
  programs' logits. The writer
  moves the bytes it is given, so where the pools differ here and not in
  `writer`, the two PROGRAMS gave their writers different rows: XLA fuses the
  producers of a custom call's operand and of a scatter's updates differently,
  and on the TPU a fusion may keep more precision than bf16 between its
  operations (`xla_allow_excess_precision`).

(`--tiny`: a rehearsal on the CPU at the tiny presets, Pallas in interpret
mode; its times mean nothing.)"""

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
sys.path[:0] = [str(ROOT), str(ROOT / "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from langstream_tpu.models import transformer as T  # noqa: E402
from langstream_tpu.models.configs import MODEL_PRESETS  # noqa: E402

CALLS = 10
TINY = {
    "keye": "tiny-sparse-moe-test", "cmda": "tiny-window-moe-test", "glm": "tiny-latent-moe-test",
}


def say(**line) -> None:
    print(json.dumps(line), flush=True)


@jax.jit
def differ(a, b):
    """(values of ``a`` whose bits are not ``b``'s, the largest difference),
    each a LAYER of the leaf, reduced where the arrays lie."""
    unequal = lax.bitcast_convert_type(a, jnp.uint16) != lax.bitcast_convert_type(b, jnp.uint16)
    rest = tuple(range(1, a.ndim))
    gap = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
    return unequal.sum(axis=rest), gap.max(axis=rest)


def config_of(model: str, tiny: bool):
    if tiny:
        return dataclasses.replace(MODEL_PRESETS[TINY[model]], attention_impl="pallas")
    import test_tpu_compile as widths

    config = {"keye": widths.KEYE, "cmda": widths.CMDA, "glm": widths.GLM}[model]
    # an eighth of the experts a chip of the cell holds: the attention half,
    # which makes and reads the rows, is the cell's
    held = max(2, (config.experts_held[1] - config.experts_held[0]) // 8)
    return dataclasses.replace(config, experts_held=(0, held))


def rows_of(pool, config, n: int, s: int, key):
    """One layer's new rows for every leaf a segment writes, as
    `_paged_write_rows` takes them, and the pool's leaves in that order."""
    names = [name for name in config.page_leaves if name != "ik"] + (
        ["ik"] if "ik" in config.page_leaves else []
    )
    leaves, rows = [], []
    for i, name in enumerate(names):
        leaf = pool[name]
        leaves.append(leaf)
        shape = (n, s, leaf.shape[-1]) if name == "ik" else (n, leaf.shape[2], s, leaf.shape[-1])
        rows.append(jax.random.normal(jax.random.fold_in(key, i), shape, leaf.dtype))
    return tuple(leaves), rows


def writer(model: str, config, page: int, s: int, pages: int, repeats: int) -> None:
    pool = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(a.ndim), a.shape, a.dtype),
        {k: v for k, v in T.make_page_pool(config, pages, page).items() if k != "win"},
    )
    leaves, rows = rows_of(pool, config, 1, s, jax.random.PRNGKey(7))
    per_row = s // page
    # the row holds all of the segment's pages but its last two: one past its
    # reservation (the sentinel), one past the table
    table = jnp.asarray([[*range(3, 3 + 2 * per_row - 1)]], jnp.int32)
    table = table.at[0, 2 * per_row - 2].set(pages)
    positions = per_row * page + jnp.arange(s)[None, :]
    layer = jnp.int32(leaves[0].shape[0] - 1)  # the last layer this group of leaves holds

    def write(by_page):
        def fn(leaves, rows, table, positions):
            return T._paged_write_rows(
                leaves, rows, layer, table, positions, page,
                config if by_page else dataclasses.replace(config, attention_impl="jnp"),
                segment=True,
            )

        return fn

    got = jax.jit(write(True))(leaves, rows, table, positions)
    want = jax.jit(write(False))(leaves, rows, table, positions)
    equal = all(int(differ(g, w)[0].sum()) == 0 for g, w in zip(got, want))
    changed = any(int(differ(g, w)[0].sum()) > 0 for g, w in zip(got, leaves))
    ms = {}
    for name, by_page in (("pages", True), ("scatter", False)):
        @functools.partial(jax.jit, donate_argnums=0)
        def loop(leaves, rows, table, positions, fn=write(by_page)):
            # the loop's index moves the rows: no call is another's common expression
            return lax.fori_loop(
                0, CALLS,
                lambda i, leaves: tuple(fn(leaves, [r * (1 + i % 2).astype(r.dtype) for r in rows],
                                           table, positions)),
                tuple(leaves),
            )

        held = jax.block_until_ready(loop(jax.tree.map(jnp.copy, leaves), rows, table, positions))
        times = []
        for _ in range(repeats):
            t = time.perf_counter()
            held = jax.block_until_ready(loop(held, rows, table, positions))
            times.append((time.perf_counter() - t) * 1e3 / CALLS)
        ms[name] = round(statistics.median(times), 4)
    say(read="writer", model=model, leaves=[list(a.shape) for a in leaves], segment=s,
        pages_mapped=per_row - 2, equal=equal, pool_changed=changed, ms_a_layer=ms)
    assert equal and changed, "the page writer's pool is not the scatter's"


def program(model: str, config, page: int, s: int, segments: int) -> None:
    from langstream_tpu.models.quant import quantize_params

    params = quantize_params(T.init_params(config, jax.random.PRNGKey(0)), config)
    table_len = segments * (s // page)
    kw = {"window_pages": table_len} if config.has_window else {}
    pool = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(a.ndim), a.shape, a.dtype) * 0.1,
        T.make_page_pool(config, table_len, page, **kw),
    )
    table = jnp.arange(table_len, dtype=jnp.int32)[None, :]
    if config.has_window:
        table = jnp.stack([table, table])

    def segment_program():
        # a function of its own a writer: each is traced once, under its rule
        def run(params, tokens, offset, pool):
            return T.paged_prefill_segment_inplace(
                params, tokens, offset, jnp.full((1,), s, jnp.int32), pool, table, config, page
            )[:2]

        return jax.jit(run)

    pools = {"pages": pool, "scatter": pool}
    runs = {name: segment_program() for name in pools}
    was = T._copies_pages
    for i in range(segments):
        tokens = jax.random.randint(jax.random.PRNGKey(100 + i), (1, s), 1, config.vocab_size)
        offset = jnp.full((1,), i * s, jnp.int32)
        logits = {}
        for name in pools:
            T._copies_pages = was if name == "pages" else (lambda *a: False)
            try:
                logits[name], pools[name] = runs[name](params, tokens, offset, pools[name])
            finally:
                T._copies_pages = was
        leaves = {}
        flat = lambda tree: jax.tree_util.tree_leaves_with_path(tree)  # noqa: E731
        for (path, g), (_, w) in zip(flat(pools["pages"]), flat(pools["scatter"])):
            count, most = differ(g, w)
            leaves[jax.tree_util.keystr(path)] = (
                "equal" if int(count.sum()) == 0
                else {"differ_a_layer": count.tolist(), "of_a_layer": int(g[0].size),
                      "max_a_layer": [round(float(m), 5) for m in most]}
            )
        a, b = (np.asarray(logits[name][0], np.float32) for name in ("pages", "scatter"))
        say(read="program", model=model, segment=i, offset=i * s, pool_leaves=leaves,
            logits_max_diff=float(np.abs(a - b).max()), logits_max=float(np.abs(b).max()),
            same_token=bool(a.argmax() == b.argmax()))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--models", default="keye,cmda,glm")
    parser.add_argument("--segments", type=int, default=3)
    parser.add_argument("--repeats", type=int, default=5)
    args = parser.parse_args()
    on_chip = jax.default_backend() == "tpu"
    if not (args.tiny or on_chip):
        print("no TPU here: --tiny rehearses on the CPU", file=sys.stderr)
        return 2
    page, s = (8, 32) if args.tiny else (64, 2048)
    say(device=jax.devices()[0].device_kind, page=page, segment=s)
    for model in args.models.split(","):
        config = config_of(model, args.tiny)
        writer(model, config, page, s, 24 if args.tiny else 512, args.repeats)
        program(model, config, page, s, args.segments)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
