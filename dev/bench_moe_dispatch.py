"""One expert layer that holds a share (`models/transformer.moe_ffn_held`),
alone on the chip at the three share-holding cells' widths (the benchmark's
own configuration files: Kimi 12 of 384 experts of 7168 x 2048, GLM 16 of 256
of 6144 x 2048, command-a-plus 16 of 128 of 4096 x 4096; top-8; no shared
expert, so the time is the router's, the rows' movement and the grouped
products'), a prefill segment of 2,048 tokens and a decode step of 16. By
hand, through the chip tool; not part of the benchmark's command.

    python3 dev/bench_moe_dispatch.py [--shapes kimi,glm,cmdaplus] [--tokens 2048,16] \
        [--hot 0,1] [--trace]

One JSON line a reading: `ms`, milliseconds a call, the median of `--repeats`
timings of 10 calls; `note`, what `attention_paths()` says of the call's
layout (`moe-dispatch[..]`); `counts`, the layer's own; and, where the call
takes passes (`ops/grouped_matmul.pass_shape`), `one_pass_ms`,
`against_one_pass` and `rows_apart`: the same call with the rule patched to
the buffer that holds every case (the layout before PR 54), the largest
difference of the two outputs over the largest output, and the rows that
differ at all (this bare layer, with no shared expert, no router bias and no
residual beside it, reads a few: PERF.md section 6, PR 54). `--hot h`
points as many of the rows along the held experts' router columns as put h
times a pass's assignments here (0: the seeded router as it is, near even;
1: a pass and what even routing adds, what a skew pays). `--trace` adds
`ops`: the call's 30 longest device operations from a profile of 10 calls,
each with its scope."""

from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
CALLS = 10
CONFIGS = {
    "kimi": "kimi-k2.5-int8-ep32-d7",
    "glm": "glm-5-int8-ep16-d7",
    "cmdaplus": "command-a-plus-05-2026-int8-ep8-d8",
}


def timed(fn, *args, repeats: int) -> float:
    """Milliseconds a call: CALLS calls launched back to back and the last
    awaited, the median of ``repeats`` such timings after one warm-up."""
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t) * 1e3 / CALLS)
    return statistics.median(times)


def traced_ops(fn, *args) -> list:
    """CALLS calls under the profiler: the 30 longest device operations as
    [short name, milliseconds a call, events a call, scope path], by the
    harness's own reduction (`reduce/scoped.load`)."""
    import tempfile

    import jax
    from reduce import scoped
    from reduce.xplane import find_trace, short_name

    with tempfile.TemporaryDirectory() as directory:
        with jax.profiler.trace(directory):
            for _ in range(CALLS):
                out = fn(*args)
            jax.block_until_ready(out)
        trace = scoped.load(find_trace(directory))
    ops: dict[str, list] = {}
    runs = [e for es in trace["executions"].values() for e in es]
    for execution in runs:
        for name, (seconds, calls) in execution["ops"].items():
            row = ops.setdefault(name, [0.0, 0])
            row[0] += seconds
            row[1] += calls
    return sorted(
        (
            [short_name(n), round(s * 1e3 / len(runs), 4), round(c / len(runs), 1),
             trace["scope_of"].get(n, "")[-70:]]
            for n, (s, c) in ops.items()
        ),
        key=lambda row: -row[1],
    )[:30]


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default="kimi,glm,cmdaplus")
    parser.add_argument("--tokens", default="2048,16")
    parser.add_argument("--hot", default="0")
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--trace", action="store_true")
    args = parser.parse_args()
    sys.path[:0] = [str(HERE), str(HERE / "benchmark")]

    import jax
    import jax.numpy as jnp
    import modelcfg

    from langstream_tpu.models import transformer as T
    from langstream_tpu.ops import attention as A
    from langstream_tpu.ops import grouped_matmul as gm

    for shape in args.shapes.split(","):
        name = CONFIGS[shape]
        config = modelcfg.model_config(modelcfg.load_json("configs", name), name)
        config = dataclasses.replace(config, n_shared_experts=0, router_bias=False)
        (first, held), d, f = config.held_experts, config.d_model, config.expert_d_ff
        keys = jax.random.split(jax.random.PRNGKey(0), 5)

        def stack(key, a, b):
            return {
                "q": jax.random.randint(key, (held, a, b), -127, 128, jnp.int8),
                "s": jnp.full((held, 1, b), 1 / (127 * a**0.5), jnp.float32),
            }

        router = jax.random.normal(keys[3], (d, config.n_experts), jnp.float32) / d**0.5
        lp = {
            "w_gate": stack(keys[0], d, f), "w_up": stack(keys[1], d, f),
            "w_down": stack(keys[2], f, d), "router": router,
        }
        for tokens in map(int, args.tokens.split(",")):
            x = jax.random.normal(keys[4], (1, tokens, d), jnp.bfloat16)
            tile = gm.row_tile(tokens, config.n_experts_per_tok, config.n_experts)
            passes = gm.pass_shape(tokens, config.n_experts_per_tok, held, config.n_experts, tile)
            layer = jax.jit(lambda x, lp: T.moe_ffn_held(x, lp, config))
            A._PATHS.clear()
            note = None  # what the call says of its layout, once, at its trace
            for hot in map(float, args.hot.split(",")):
                # the first rows point along the held experts' router columns:
                # each puts its min(k, held) choices here, ``hot`` passes' worth
                here = min(held, config.n_experts_per_tok)
                n_hot = min(tokens, round(hot * (passes[0] if passes else 0) / here))
                along = 8.0 * router[:, first : first + held].sum(1) * d**0.5
                xs = x.at[0, :n_hot].set(along.astype(x.dtype))
                out, counts = layer(xs, lp)
                note = note or {
                    k: v for k, v in A.attention_paths().items() if k.startswith("moe-d")
                }
                line = dict(
                    shape=shape, tokens=tokens, hot=hot, device=jax.devices()[0].device_kind,
                    note=note,
                    counts=dict(zip(T.MOE_HELD_COUNTS, (int(c) for c in counts))),
                    ms=round(timed(layer, xs, lp, repeats=args.repeats), 4),
                )
                if passes:
                    rule, gm.pass_shape = gm.pass_shape, lambda *a: None
                    try:
                        one = jax.jit(lambda x, lp: T.moe_ffn_held(x, lp, config))
                        want, _ = one(xs, lp)
                        line["one_pass_ms"] = round(timed(one, xs, lp, repeats=args.repeats), 4)
                    finally:
                        gm.pass_shape = rule
                    apart = jnp.abs(out.astype(jnp.float32) - want.astype(jnp.float32))
                    line["against_one_pass"] = float(apart.max() / jnp.abs(want).max())
                    line["rows_apart"] = int((apart.max(-1) > 0).sum())
                if args.trace:
                    line["ops"] = traced_ops(layer, xs, lp)
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
