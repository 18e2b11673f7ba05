"""The two prices of a decode step's selected read, and the ranking's, alone on
the chip at the Keye cell's shapes (8 rows, 4 KV heads of 128, pages of 64,
top-k 2,048) over tables of 272 pages (the cell's: 8.5 x the top-k), 544 and
1,088 (17 x and 34 x: both sides of `models/transformer._WALK_TABLE_PER_TOPK`,
which rests on these readings). By hand, through the chip tool; not part of the
benchmark's command.

    python3 dev/bench_selected_read.py [--tiny] [--tables 272,544,1088]

One JSON line a reading, milliseconds a call (one layer), the median of
`--repeats` timings of a jitted loop of 20 calls, at rows of `tokens` each in a
table of `table` pages:

- `walk`: `ragged_paged_selected_attention` under a random selection of 2,048,
  the kernel alone, and `GB/s` of the K and V it walks;
- `walk-unmasked`: `ragged_paged_decode_attention` over the same rows (what the
  mask's operand and its `where` add to that);
- `walk-whole` / `gather-whole`: the two sides of the rule as a decode step
  pays them, each with the indexer's scores over the whole table and its own
  ranking: `_decode_index_scores`, `_select_mask` and the kernel, against
  `_sparse_decode_attention` (scores, `lax.top_k`, the gather of the selected
  rows, the attention over them). The rule compares THESE;
- `walk-against-gather`: the largest difference between the two reads of one
  ranked selection (rows past the top-k, one under it, one of length 0);
- `against-exact`: each read's error against the same selection's attention in
  float32 at the highest precision over the same bf16 pool, `layers` draws of
  8 rows: mean and largest absolute error, and how many outputs each read has
  nearer the exact one (a read that rounds no worse than the other wins about
  half);
- `select-count` / `select-top_k`: `_select_mask` (32 counts of the row), and
  `lax.top_k`'s k-th value as the threshold, over `[8, 17408]` float32.

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

from langstream_tpu.models import transformer as T  # noqa: E402
from langstream_tpu.models.configs import MODEL_PRESETS  # noqa: E402
from langstream_tpu.ops import attention as A  # noqa: E402

CALLS = 20


def timed(fn, *args, repeats: int) -> float:
    """Milliseconds a call of ``fn(i, *args)``: a jitted loop of CALLS calls
    whose results are summed (nothing is dead code), the median of
    ``repeats`` timings after one warm-up. Every array is an argument: one a
    function closes over would be a constant of its program."""

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


def exact_read(q, pk, pv, tables, layer, chosen, config):
    """[B, H*D] float32: attention of each row's query over its ``chosen``
    columns, every product in float32 at the highest precision."""
    b, h, d = q.shape
    hkv = pk.shape[2]
    k, v = (
        leaf[layer, tables].astype(jnp.float32).transpose(0, 2, 1, 3, 4).reshape(b, hkv, -1, d)
        for leaf in (pk, pv)
    )
    qf = q.astype(jnp.float32).reshape(b, hkv, h // hkv, d)
    s = jnp.einsum("bhgd,bhtd->bhgt", qf, k, precision="highest") / d**0.5
    s = jnp.where(chosen[:, None, None, :], s, -jnp.inf)
    p = jnp.where(chosen[:, None, None, :], jnp.exp(s - s.max(-1, keepdims=True)), 0)
    out = jnp.einsum("bhgt,bhtd->bhgd", p / p.sum(-1, keepdims=True), v, precision="highest")
    return jnp.nan_to_num(out).reshape(b, h * d)


def main(tiny: bool, repeats: int, tables: list[int]) -> int:
    on_chip = jax.default_backend() == "tpu"
    if not (tiny or on_chip):
        print("no TPU here: --tiny rehearses on the CPU", file=sys.stderr)
        return 2
    base = MODEL_PRESETS["tiny-sparse-moe-test"]
    if tiny:
        page, rows, tables = 8, 2, tables or [8, 16]
    else:
        page, rows, tables = 64, 8, tables or [272, 544, 1088]
        base = dataclasses.replace(
            base, n_heads=32, n_kv_heads=4, head_dim=128, index_topk=2048,
            index_n_heads=16, index_head_dim=64, mrope_section=(16, 24, 24), dtype="bfloat16",
        )
    config = dataclasses.replace(base, attention_impl="pallas")
    dtype = jnp.bfloat16 if on_chip else jnp.float32
    say = lambda **line: print(json.dumps(line), flush=True)  # noqa: E731
    say(device=jax.devices()[0].device_kind, rows=rows, tables=tables, page=page,
        topk=config.index_topk,
        heads=[config.n_heads, config.n_kv_heads, config.resolved_head_dim])
    for table in tables:
        measure(config, dtype, on_chip, repeats, rows, table, page, say, first=table == tables[0])
    return 0


def measure(config, dtype, on_chip, repeats, rows, table, page, say, first) -> None:
    """Every reading at one table size; the arrays die with the call."""
    h, hkv, d, topk = config.n_heads, config.n_kv_heads, config.resolved_head_dim, config.index_topk
    hi, di = config.index_n_heads, config.index_head_dim
    t = table * page
    layers = 4 if first else 2  # a pool of 1,088 pages a row: 0.57 GB a layer and leaf
    sweeps = (t // 4, t // 2, 18 * t // 25, t) if first else (t // 2, t)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    pool = (layers, rows * table, hkv, page, d)
    pk, pv = (jax.random.normal(k, pool, dtype) for k in keys[:2])
    pik = jax.random.normal(keys[2], (layers, rows * table, page, config.index_key_width), dtype)
    q = jax.random.normal(keys[3], (rows, h, d), dtype)
    q_idx = jax.random.normal(keys[4], (rows, hi, di), dtype)
    w = jax.random.normal(keys[5], (rows, hi), jnp.float32)
    scores = jax.random.normal(keys[6], (rows, t), jnp.float32)
    tables = jax.random.permutation(keys[7], rows * table).reshape(rows, table).astype(jnp.int32)

    def lengths_of(tokens):
        return jnp.full((rows,), tokens, jnp.int32)

    kv = (q, pk, pv)
    index = (q_idx, w, pik)

    def walk(i, kv, tables, lengths, chosen):
        return A.ragged_paged_selected_attention(
            *kv, lengths, tables, i % layers, chosen, config, page, interpret=not on_chip)

    def unmasked(i, kv, tables, lengths):
        return A.ragged_paged_decode_attention(
            *kv, lengths, tables, i % layers, config, page, interpret=not on_chip)

    def ranked(layer, tables, lengths, index):
        visible = jnp.arange(t)[None, :] < lengths[:, None]
        return T._select_mask(
            T._decode_index_scores(*index, tables, layer, config, page), visible, topk)

    def walk_whole(i, kv, tables, lengths, index):
        return walk(i, kv, tables, lengths, ranked(i % layers, tables, lengths, index))

    def gather_whole(i, kv, tables, lengths, index):
        q, pk, pv = kv
        return T._sparse_decode_attention(
            q, *index[:2], pk, pv, index[2], tables, i % layers, lengths, config, page)

    for tokens in sweeps:
        lengths = lengths_of(tokens)
        visible = jnp.arange(t)[None, :] < lengths[:, None]
        chosen = T._select_mask(scores, visible, topk)
        walked = rows * tokens * 2 * hkv * d * jnp.dtype(dtype).itemsize
        at = dict(table=table, tokens=tokens)
        ms = timed(walk, kv, tables, lengths, chosen, repeats=repeats)
        say(read="walk", **at, ms=ms, gb_per_s=walked / ms / 1e6)
        ms = timed(unmasked, kv, tables, lengths, repeats=repeats)
        say(read="walk-unmasked", **at, ms=ms, gb_per_s=walked / ms / 1e6)
        say(read="walk-whole", **at, ms=timed(walk_whole, kv, tables, lengths, index, repeats=repeats))
        say(read="gather-whole", **at, ms=timed(gather_whole, kv, tables, lengths, index, repeats=repeats))
    if not first:
        return
    # the two reads of one selection agree to the pool's rounding ...
    lengths = lengths_of(sweeps[-2]).at[0].set(topk // 2).at[1].set(0)
    one = walk_whole(jnp.int32(1), kv, tables, lengths, index).astype(jnp.float32)
    other = gather_whole(jnp.int32(1), kv, tables, lengths, index).astype(jnp.float32)
    say(read="walk-against-gather", max_abs_diff=float(jnp.abs(one - other).max()),
        max_abs=float(jnp.abs(other).max()),
        selected=[int(n) for n in ranked(1, tables, lengths, index).sum(-1)])
    # ... and neither is nearer the exact attention over that selection
    lengths = lengths_of(sweeps[-2])
    errs = {"walk": [], "gather": []}
    for layer in range(layers):
        chosen = ranked(layer, tables, lengths, index)
        exact = exact_read(q, pk, pv, tables, layer, chosen, config)
        errs["walk"].append(jnp.abs(walk(jnp.int32(layer), kv, tables, lengths, chosen) - exact))
        errs["gather"].append(jnp.abs(gather_whole(jnp.int32(layer), kv, tables, lengths, index) - exact))
    errs = {name: jnp.concatenate(e).astype(jnp.float32) for name, e in errs.items()}
    say(read="against-exact", outputs=int(errs["walk"].size), exact_abs_mean=float(jnp.abs(exact).mean()),
        **{f"{name}_{stat}": float(getattr(e, stat)()) for name, e in errs.items() for stat in ("mean", "max")},
        walk_nearer=int((errs["walk"] < errs["gather"]).sum()),
        gather_nearer=int((errs["gather"] < errs["walk"]).sum()))
    visible = jnp.arange(t)[None, :] < lengths_of(sweeps[-2])[:, None]

    def by_count(i, scores, visible):
        return T._select_mask(scores + i.astype(jnp.float32), visible, topk)

    def by_top_k(i, scores, visible):
        masked = jnp.where(visible, scores + i.astype(jnp.float32), -jnp.inf)
        kth = lax.top_k(masked, topk)[0][:, -1:]
        return visible & (masked >= kth)

    say(read="select-count", ms=timed(by_count, scores, visible, repeats=repeats))
    say(read="select-top_k", ms=timed(by_top_k, scores, visible, repeats=repeats))


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--tables", default="", help="pages a row, e.g. 272,544,1088")
    args = parser.parse_args()
    raise SystemExit(main(args.tiny, args.repeats, [int(n) for n in args.tables.split(",") if n]))
