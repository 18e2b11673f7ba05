#!/usr/bin/env python3
"""One benchmark cell through `benchmark/run.py`'s own `main` with `--trace 1`,
and one more evidence line before the result: the device operations of ONE
program under ONE scope, each with its self time, from the window's profile.

    python3 dev/bench_scope_ops.py [--root <checkout>] [--program <jit name>] [--scope <scope>] \
        [--match <regex>] --workload <cell> --seed <n> --seconds <s> --trace 1

`{"phase": "scope_ops", ...}` holds, over the WHOLE executions of `program`
(default `_paged_segment_and_sample`) inside the traced seconds: how many
there were, their device seconds, the seconds under `scope` (default
`kv_pool.write`), every operation under it as `[short name, seconds, calls,
scope path]`, the longest first. Traced or not, `{"phase": "segments", ...}`
holds `stats()["segment-writes"]` where the engine has the counter (PR 48;
None at a parent without it) and the window's `engine.prefill_segment` spans:
how many, and least, quartiles and most of their device milliseconds a thousand
computed tokens (what `prefill_segment_ms_per_1k_tokens.drain` sums), and
`moe_spilled_assignment_share` (PR 54: `moe_spilled` over `moe_local` x 100 by
the harness's `span_ratio` from `layer_metrics/moe_spilled_assignment_share.json`,
which `BENCHMARK.json` does not name yet; None where no span has the count). The
reduction is the harness's own (`reduce/scoped.load`, `scope_seconds`' rule: an
operation counts under the scope path of its `tf_op`), so the figure is what a
per-layer metric over that scope would read. An operation the compiler made
itself carries no scope: the pool's row scatter is such a fusion (`kind=kCustom`
over the leaf seen as `bf16[L*P*Hkv*ps, D]`), so `--match` names a regex over
the event's HLO line and `matched` / `matched_s` list what it finds in the
program whatever its scope (default: a fusion whose result is a 7-digit row
count of `D` values, and the page writer's call). The benchmark's files are not
touched: `run.run_window` is wrapped at run time. `--root` names the checkout
whose benchmark and program run (default: this one), so a parent commit
unpacked elsewhere reads the same way.
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

POOL_WRITES = r"= bf16\[\d{7},\d+\]\S* fusion\(|paged_insert_pages"


def say_scope_ops(run, program: str, scope: str, match: str) -> None:
    """Wrap ``run.run_window`` so that a traced window's operations under
    ``scope`` inside ``program``, and those ``match`` finds, are emitted."""
    run_window = run.run_window

    async def run_window_and_say(**kwargs):
        window = await run_window(**kwargs)
        # traced or not: the window's segments as the engine saw them
        spans = [s["attributes"] for s in window["spans"] if s["name"] == "engine.prefill_segment"]
        per_1k = sorted(
            a["device_ms"] * 1e3 / a["computed_tokens"]
            for a in spans if a.get("device_ms") and a.get("computed_tokens")
        )
        from readers import span_ratio

        try:  # by the harness's reader, from the file a `benchmark` PR will name (PERF.md §7)
            spilled = span_ratio.read(
                run.load_json("layer_metrics", "moe_spilled_assignment_share"), window
            )
        except FileNotFoundError:  # a checkout from before PR 54
            spilled = None
        run.emit(
            phase="segments", segment_writes=window["stats"].get("segment-writes"),
            spans=len(spans), device_ms_per_1k_tokens=[
                round(per_1k[int(q * (len(per_1k) - 1))], 3) for q in (0, 0.25, 0.5, 0.75, 1)
            ] if per_1k else None,
            moe_spilled_assignment_share=spilled,
        )
        if window.get("trace_dir"):
            from reduce import scoped
            from reduce.xplane import find_trace, short_name

            trace = scoped.load(find_trace(window["trace_dir"]))
            executions = trace["executions"].get("jit_" + program, [])
            ops: dict[str, list] = {}
            total = 0.0
            for execution in executions:
                for name, (seconds, calls) in execution["ops"].items():
                    total += seconds
                    row = ops.setdefault(name, [0.0, 0])
                    row[0] += seconds
                    row[1] += calls

            def rows(keep):
                return sorted(
                    (
                        [short_name(n), round(s, 6), c, trace["scope_of"].get(n, "")[-160:]]
                        for n, (s, c) in ops.items() if keep(n)
                    ),
                    key=lambda row: -row[1],
                )

            scoped_rows = rows(lambda n: scoped.under(trace["scope_of"], n, {scope}))
            matched = rows(lambda n: re.search(match, n))
            run.emit(
                phase="scope_ops", program=program, scope=scope, executions=len(executions),
                program_s=round(total, 6), scope_s=round(sum(r[1] for r in scoped_rows), 6),
                ops=scoped_rows[:60], match=match,
                matched_s=round(sum(r[1] for r in matched), 6), matched=matched[:40],
            )
        return window

    run.run_window = run_window_and_say


def main() -> int:
    argv = sys.argv[1:]
    root = Path(__file__).resolve().parent.parent
    named = {
        "--root": str(root), "--program": "_paged_segment_and_sample",
        "--scope": "kv_pool.write", "--match": POOL_WRITES,
    }
    while argv[:1] and argv[0] in named:
        named[argv[0]], argv = argv[1], argv[2:]
    root = Path(named["--root"]).resolve()
    sys.path[:0] = [str(root), str(root / "benchmark")]
    import run

    say_scope_ops(run, named["--program"], named["--scope"], named["--match"])
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
