"""What the client waited for its first chunk beyond what the engine spent
on the request: client time to first chunk minus the named `engine.*` spans
of the same request, joined on the trace id the client chose. What is left
is gateway, broker, agent runtime and the way back."""

from __future__ import annotations

from typing import Optional

from metrics import percentile, ttft_ms


def read(definition: dict, ctx: dict) -> Optional[float]:
    inside: dict[str, float] = {}
    for span in ctx["spans"]:
        if span["name"] in definition["spans"]:
            inside[span["traceId"]] = inside.get(span["traceId"], 0.0) + span["durationMs"]
    outside = [
        ttft_ms(r) - inside[r["id"]]
        for r in ctx["requests"]
        if r.get("t_first") is not None and r["id"] in inside
    ]
    if not outside:
        return None
    return percentile(outside, definition["percentile"])
