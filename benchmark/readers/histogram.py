"""One of the engine's streaming histograms (`stats()["histograms"]`), reset
at the window's start. `mean` is sum over count and exact; `p50`/`p90`/`p99`
are interpolated inside log-spaced buckets four to a decade, so they are
coarse and stay per-layer."""

from __future__ import annotations

from typing import Optional


def read(definition: dict, ctx: dict) -> Optional[float]:
    snapshot = ctx["histograms"].get(definition["histogram"])
    if not snapshot or not snapshot["count"]:
        return None
    statistic = definition["statistic"]
    if statistic == "mean":
        value = snapshot["sum"] / snapshot["count"]
    else:
        value = snapshot[statistic]
    return value * definition.get("scale", 1.0)
