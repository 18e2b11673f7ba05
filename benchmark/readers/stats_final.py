"""One key of `engine.stats()` as read once the window has ended (`key`),
optionally as a share of another (`over`): a gauge the engine itself keeps
over the window, such as a peak since the histograms were last reset (the
window's start), which the 100 ms samples (`stats_sample.py`, a fixed set of
keys) do not carry. An engine without the key reads as nothing."""

from __future__ import annotations

from typing import Optional


def read(definition: dict, ctx: dict) -> Optional[float]:
    stats = ctx.get("stats") or {}
    key, over = definition["key"], definition.get("over")
    if key not in stats or (over and not stats.get(over)):
        return None
    value = stats[key] / stats[over] if over else stats[key]
    return value * definition.get("scale", 1.0)
