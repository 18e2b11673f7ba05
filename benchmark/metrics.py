"""The arithmetic from client records to end-to-end metrics. No jax."""

from __future__ import annotations

import asyncio
import math
import time
from typing import Iterable, Optional


class LagWatch:
    """How long an event loop ever stood still: a task that sleeps 20 ms at
    a time and keeps its worst overshoot. Both the load generator and the
    serving process run one, so a stall shows on the side it happened."""

    def __init__(self) -> None:
        self.max_s = 0.0

    async def run(self) -> None:
        while True:
            before = time.monotonic()
            await asyncio.sleep(0.02)
            self.max_s = max(self.max_s, time.monotonic() - before - 0.02)


def percentile(values: Iterable[float], p: float) -> float:
    """Nearest rank: the smallest value with at least p of the sample at or
    below it. A value that was measured, never an interpolation."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(p * len(ordered)))
    return ordered[rank - 1]


def ttft_ms(request: dict) -> Optional[float]:
    """From the instant the request was DUE to the first chunk at the
    client, so a generator or a server that runs late is counted."""
    if request.get("t_first") is None:
        return None
    return (request["t_first"] - request["due"]) * 1e3


def tpot_ms(request: dict) -> Optional[float]:
    """(last chunk - first chunk) / (tokens - 1). Chunks grow 1, 2, 4 .. 20
    tokens, so a per-chunk gap is not a per-token gap; the per-request mean
    is."""
    if not request.get("done") or request["tokens"] < 2:
        return None
    return (request["t_last"] - request["t_first"]) / (request["tokens"] - 1) * 1e3


def tokens_in_window(chunks: Iterable[tuple[float, int]], start: float, end: float) -> int:
    return sum(n for t, n in chunks if start <= t < end)


def end_to_end(requests: list[dict], chunks: list, start: float, seconds: float) -> dict:
    """Every end-to-end metric the records support; the cell's own list in
    BENCHMARK.json picks which are reported."""
    ttfts = [v for v in map(ttft_ms, requests) if v is not None]
    tpots = [v for v in map(tpot_ms, requests) if v is not None]
    out = {"gen_tokens_per_s": tokens_in_window(chunks, start, start + seconds) / seconds}
    for name, values in (("ttft", ttfts), ("tpot", tpots)):
        if values:
            out[f"{name}_p50_ms"] = percentile(values, 0.50)
            out[f"{name}_p95_ms"] = percentile(values, 0.95)
            out[f"{name}_mean_ms"] = sum(values) / len(values)
    return out
