#!/usr/bin/env python3
"""One benchmark cell through `benchmark/run.py`'s own `main`, with one more
evidence line before the result: how the engine decided the window's launches.

    python3 dev/bench_launches.py [--root <checkout>] --workload <cell> --seed <n> --seconds <s> --trace <0|1>

`{"phase": "launches", ...}` holds `stats()["launches"]` (restarted with the
window; null for a checkout older than the counter) and, from the window's
`engine.iteration` spans, for each reason the count and, as least, quartiles
and most, `await` (the wait for an arrival or the deadline) and how long the
thread then blocked on the chunk ahead (`wait`): after a launch at the
deadline that is the margin less the deadline estimate's error, so a value
near 0 is a launch that came almost late. The benchmark's files are not touched: `run.run_window` is wrapped at
run time. `--root` names the checkout whose benchmark and program run
(default: this one), so a parent commit unpacked elsewhere reads the same way.
With `BENCH_SPANS_OUT=<file>` the window's `engine.*` spans are written there.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
from pathlib import Path


def quartiles(values: list[float]) -> list[float]:
    if len(values) < 2:
        return [round(v, 3) for v in values]
    return [round(v, 3) for v in (min(values), *statistics.quantiles(values, n=4), max(values))]


def say_launches(run) -> None:
    """Wrap ``run.run_window`` so that the window's launches are emitted."""
    run_window = run.run_window

    async def run_window_and_say(**kwargs):
        window = await run_window(**kwargs)
        frames = [s["attributes"] for s in window["spans"] if s["name"] == "engine.iteration"]
        by_reason = {}
        for reason in sorted({f.get("launch") or "" for f in frames} - {""}):
            mine = [f for f in frames if f.get("launch") == reason]
            by_reason[reason] = {
                "iterations": len(mine),
                "late": sum(bool(f.get("late")) for f in mine),
                "await_ms": quartiles([f["phase_ms"].get("await", 0.0) for f in mine]),
                "blocked_on_the_chunk_ahead_ms": quartiles([f["phase_ms"]["wait"] for f in mine]),
            }
        run.emit(
            phase="launches", launches=window["stats"].get("launches"),
            iteration_spans=len(frames), by_reason=by_reason,
        )
        if os.environ.get("BENCH_SPANS_OUT"):
            # every `engine.*` span of the window, for a reading by hand
            Path(os.environ["BENCH_SPANS_OUT"]).write_text(json.dumps(window["spans"]))
        return window

    run.run_window = run_window_and_say


def main() -> int:
    argv = sys.argv[1:]
    root = Path(__file__).resolve().parent.parent
    if argv[:1] == ["--root"]:
        root, argv = Path(argv[1]).resolve(), argv[2:]
    sys.path[:0] = [str(root), str(root / "benchmark")]
    import run

    say_launches(run)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
