#!/usr/bin/env python3
"""One benchmark cell through `benchmark/run.py`'s own `main`, with one more
evidence line before the result: where the time to ready went.

    python3 dev/bench_startup.py [--root <checkout>] --workload <cell> --seed <n> --seconds <s> --trace <0|1>

`{"phase": "startup", ...}` holds, as read when the window has ended (what the
harness's `stats_final` reader sees): `stats`, the engine's `startup-*` keys
(frozen when its warm-up ended) and the process's `process-*` keys
(docs/SERVING.md §12, "Start-up"); `metrics`, the ten start-up metrics of
`benchmark/layer_metrics/` (`startup_engine_*`, `setup_*`) computed from those
by the harness's own reader; `account`, the process's compile account whole
(counts, the events heard and what the listeners took); `kernels`, instances
traced by kernel; `programs`, the attributes of every `engine.startup.program`
span in dispatch order, and `spans`, the other `engine.startup*` spans, the
provider's `engine.startup.tokenizer` and `.weights` among them, with their
wall `start` (taken before the window, which clears the tracer); `by_name`, the account's
table, the most seconds first (rows under 10 ms summed in `rest_s`). The
harness's own phase lines (`weights`, `engine`, `check-time`, `ready`) stand
beside it in the output. The benchmark's files are not touched:
`run.run_window` is wrapped at run time. `--root` names the checkout whose
benchmark and program run (default: this one); one without the account
(before PR 53) says `"startup": null`.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent
METRICS = (
    "startup_engine_s", "startup_engine_trace_s", "startup_engine_lower_s",
    "startup_engine_backend_s", "setup_compile_trace_s", "setup_compile_lower_s",
    "setup_compile_backend_s", "setup_cache_retrieval_s", "setup_cache_hit_share",
    "setup_kernel_instances_traced",
)


def say_startup(run) -> None:
    """Wrap ``run.run_window`` so that the start-up's account is emitted."""
    run_window = run.run_window

    async def run_window_and_say(**kwargs):
        try:
            from langstream_tpu.compile_account import ACCOUNT
            from langstream_tpu.tracing import TRACER
        except ImportError:
            run.emit(phase="startup", startup=None)
            return await run_window(**kwargs)
        from readers import stats_final

        spans = [s for s in TRACER.spans(limit=2048) if s["name"].startswith("engine.startup")]
        window = await run_window(**kwargs)
        stats = window["stats"]
        metrics = {}
        for name in METRICS:
            definition = json.loads((HERE / "benchmark" / "layer_metrics" / f"{name}.json").read_text())
            metrics[name] = stats_final.read(definition, {"stats": stats})
        table = ACCOUNT.report()
        shown = [r for r in table if r["trace"] + r["lower"] + r["backend"] >= 0.01]
        run.emit(
            phase="startup",
            stats={k: v for k, v in stats.items() if k.startswith(("startup-", "process-"))},
            metrics=metrics, account=ACCOUNT.snapshot(), kernels=ACCOUNT.kernels(),
            programs=[
                {"ms": s["durationMs"], **s["attributes"]}
                for s in spans if s["name"] == "engine.startup.program"
            ],
            spans=[
                {"name": s["name"], "start": round(s["start"], 3), "ms": s["durationMs"],
                 "status": s["status"], **s["attributes"]}
                for s in spans if s["name"] != "engine.startup.program"
            ],
            by_name=[
                {k: round(v, 3) if isinstance(v, float) else v for k, v in r.items()}
                for r in shown
            ],
            rest_s=round(sum(
                r["trace"] + r["lower"] + r["backend"] for r in table[len(shown):]), 3),
        )
        return window

    run.run_window = run_window_and_say


def main() -> int:
    argv = sys.argv[1:]
    root = HERE
    if argv[:1] == ["--root"]:
        root, argv = Path(argv[1]).resolve(), argv[2:]
    sys.path[:0] = [str(root), str(root / "benchmark")]
    import run

    say_startup(run)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
