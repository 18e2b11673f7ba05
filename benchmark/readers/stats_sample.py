"""A statistic over `engine.stats()` sampled every 100 ms in the window:
the mean or the peak of one key, optionally as a share of another."""

from __future__ import annotations

from typing import Optional


def read(definition: dict, ctx: dict) -> Optional[float]:
    key, over = definition["key"], definition.get("over")
    values = [
        s[key] / s[over] if over else s[key]
        for s in ctx["stats_samples"]
        if key in s and (not over or s.get(over))
    ]
    if not values:
        return None
    value = max(values) if definition["statistic"] == "max" else sum(values) / len(values)
    return value * definition.get("scale", 1.0)
