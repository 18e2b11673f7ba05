"""The load generator: a child process that never imports jax, so that its
clients and the engine thread do not share one interpreter lock.

    python benchmark/loadgen.py <plan.json> <result.json>

The plan names the traffic kind, the gateway urls, the window (`t0` on
CLOCK_MONOTONIC, which parent and child share) and every request. The result
holds one record per request with its chunk times, and how late the
generator ran.
"""

from __future__ import annotations

import asyncio
import importlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from metrics import LagWatch  # noqa: E402


async def _drive(kind, plan: dict) -> tuple[dict, float]:
    lag = LagWatch()
    watcher = asyncio.ensure_future(lag.run())
    try:
        return await kind.drive(plan), lag.max_s
    finally:
        watcher.cancel()


def main(argv: list[str]) -> int:
    plan_path, result_path = argv
    plan = json.loads(Path(plan_path).read_text())
    kind = importlib.import_module(f"traffic_kinds.{plan['kind']}")
    result, lag = asyncio.run(_drive(kind, plan))
    if "jax" in sys.modules:
        raise RuntimeError("the load generator imported jax")
    late = sorted(
        r["sent"] - r["due"] for r in result["requests"] if r.get("sent") is not None
    )
    result["generator_late_s"] = {
        "p50": late[len(late) // 2] if late else None,
        "max": late[-1] if late else None,
        "loop_lag_max": lag,
    }
    Path(result_path).write_text(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
