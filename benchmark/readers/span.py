"""A percentile of the durations of one kind of span, over the spans the
harness drained from the tracer's ring during the window."""

from __future__ import annotations

from typing import Optional

from metrics import percentile


def read(definition: dict, ctx: dict) -> Optional[float]:
    durations = [s["durationMs"] for s in ctx["spans"] if s["name"] == definition["span"]]
    if not durations:
        return None
    return percentile(durations, definition["percentile"])
