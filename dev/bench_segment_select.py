"""A layer's selection of a segment, alone on the chip at the Keye cell's shapes
(one row, 2,048 queries, an indexer of 16 heads x 64, top-k 2,048): the one
call `ops/attention.segment_select` against what it replaced, `index_scores`
and then `models/transformer._select_mask` in XLA, over a table of 17,408
columns at offsets 2,048 / 8,192 / 15,360 and one of 34,816 (a query tile of 64
rows). By hand, through the chip tool; not part of the benchmark's command.

    python3 dev/bench_segment_select.py [--tiny] [--tables 17408,34816]

One JSON line a reading, milliseconds a call (one layer), the median of
`--repeats` timings of a jitted loop of 10 calls:

- `select`: `segment_select`, scores and ranking in one call;
- `xla`: `index_scores` + `_select_mask` under the causal mask, to `int8`;
- `scores`: `index_scores` alone (what both pay for the products);
- `equal`: whether the two selections are one set, query by query (asserted),
  with the fewest and most columns a query kept; `ties`: the same over scores
  of few values and shut ReLUs, where the tie rule runs.

(`--tiny`: a rehearsal on the CPU, Pallas in interpret mode; its times mean
nothing.)"""

from __future__ import annotations

import argparse
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
from langstream_tpu.ops import attention as A  # noqa: E402

CALLS = 10


def timed(fn, *args, repeats: int) -> float:
    """Milliseconds a call of ``fn(i, *args)``: a jitted loop of CALLS calls
    whose results are summed (nothing is dead code), the median of
    ``repeats`` timings after one warm-up."""

    @jax.jit
    def loop(*args):
        def body(i, total):
            return total + fn(i, *args).astype(jnp.int32).sum()

        return lax.fori_loop(0, CALLS, body, jnp.int32(0))

    jax.block_until_ready(loop(*args))
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        jax.block_until_ready(loop(*args))
        times.append((time.perf_counter() - t) * 1e3 / CALLS)
    return statistics.median(times)


def main(tiny: bool, repeats: int, tables: list[int]) -> int:
    on_chip = jax.default_backend() == "tpu"
    if not (tiny or on_chip):
        print("no TPU here: --tiny rehearses on the CPU", file=sys.stderr)
        return 2
    if tiny:
        s, hi, di, topk, dtype = 128, 3, 16, 64, jnp.float32
        tables = tables or [1024]
    else:
        s, hi, di, topk, dtype = 2048, 16, 64, 2048, jnp.bfloat16
        tables = tables or [17408, 34816]
    say = lambda **line: print(json.dumps(line), flush=True)  # noqa: E731
    say(device=jax.devices()[0].device_kind, queries=s, heads=[hi, di], topk=topk, tables=tables)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, s, hi, di), dtype)
    w = jax.random.normal(keys[1], (1, s, hi), jnp.float32)
    for t in tables:
        k = jax.random.normal(keys[2], (1, t, di), dtype)
        first = t == tables[0]
        # a prompt's second segment, one in its middle, the table's last
        for offset in ((s, 4 * s, t - s) if first else (t - s,)):
            measure(q, w, k, offset, topk, on_chip, repeats, say)
        if first:
            # scores of few values, heads with their ReLU shut: rows tie at
            # their threshold, +0.0 among them
            few = (jnp.round(q), jnp.round(w), jnp.round(k))
            offsets = jnp.full((1,), t - s, jnp.int32)
            got, want = select(*few, offsets, topk, on_chip), by_xla(*few, offsets, topk, on_chip)
            tied = int((A.index_scores(*few, offsets, interpret=not on_chip)[0, -1] == 0).sum())
            say(read="ties", offset=t - s, equal=bool((got == want).all()), zeros_in_a_row=tied)
            assert bool((got == want).all())
    return 0


def select(q, w, k, offsets, topk, on_chip):
    return A.segment_select(q, w, k, offsets, topk, interpret=not on_chip)


def by_xla(q, w, k, offsets, topk, on_chip):
    s, t = q.shape[1], k.shape[1]
    causal = jnp.arange(t)[None, None, :] <= (offsets[:, None] + jnp.arange(s))[:, :, None]
    scores = A.index_scores(q, w, k, offsets, interpret=not on_chip)
    return T._select_mask(scores, causal, topk).astype(jnp.int8)


def measure(q, w, k, offset, topk, on_chip, repeats, say) -> None:
    t = k.shape[1]
    offsets = jnp.full((1,), offset, jnp.int32)
    at = dict(table=t, offset=offset, blocks=list(A.select_blocks(q.shape[1], t)))
    # the loop's index moves the queries: no call is another's common expression
    turn = lambda i, q: q * (1 + i % 2).astype(q.dtype)  # noqa: E731
    say(read="select", **at, ms=timed(
        lambda i, q, w, k, o: select(turn(i, q), w, k, o, topk, on_chip), q, w, k, offsets,
        repeats=repeats))
    say(read="xla", **at, ms=timed(
        lambda i, q, w, k, o: by_xla(turn(i, q), w, k, o, topk, on_chip), q, w, k, offsets,
        repeats=repeats))
    say(read="scores", **at, ms=timed(
        lambda i, q, w, k, o: (A.index_scores(turn(i, q), w, k, o, interpret=not on_chip) > 0),
        q, w, k, offsets, repeats=repeats))
    got = jax.jit(select, static_argnums=(4, 5))(q, w, k, offsets, topk, on_chip)
    want = jax.jit(by_xla, static_argnums=(4, 5))(q, w, k, offsets, topk, on_chip)
    kept = got.astype(jnp.int32).sum(-1)
    say(read="equal", **at, equal=bool((got == want).all()),
        differ=int((got != want).sum()), kept=[int(kept.min()), int(kept.max())])
    assert bool((got == want).all()), "the kernel's set is not _select_mask's"


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--tables", default="", help="columns of table, e.g. 17408,34816")
    args = parser.parse_args()
    raise SystemExit(main(args.tiny, args.repeats, [int(n) for n in args.tables.split(",") if n]))
