"""Open loop: independent users arrive on a Poisson schedule, each a
single-turn chat session on the chat websocket of its output cap. Requests
are sent when they are due whether or not earlier ones have been answered,
and each is timed from the instant it was due. No jax."""

from __future__ import annotations

import asyncio
import json
import math
import time

import numpy as np

from .common import new_record, note_chunk, order_rng, parse_push, requests_for, stratified


def schedule(params: dict, seed: int, seconds: float, vocab_size: int) -> list[dict]:
    """`rate_per_s` fixed in the file. The inter-arrival gaps are the
    stratified quantiles of Exp(rate) in the file's order, so every seed
    offers the same arrivals and sizes and draws only the token ids."""
    rate = float(params["rate_per_s"])
    n = max(1, round(rate * seconds))
    order = order_rng(params)
    gaps = order.permutation(stratified(n, lambda u: -math.log1p(-u) / rate))
    # the first request opens the window, the last falls inside it
    due = (np.cumsum(gaps) - gaps[0]) * (seconds / gaps.sum())
    requests = requests_for(params, seed, n, vocab_size, order)
    for request, t in zip(requests, due):
        request["due_s"] = float(max(t, 0.0))
    return requests


async def _session(http, plan: dict, request: dict) -> dict:
    import aiohttp

    due = plan["t0"] + request["due_s"]
    await asyncio.sleep(max(0.0, due - time.monotonic()))
    out = new_record(request, due)
    out["sent"] = time.monotonic()
    url = f"{plan['urls'][str(request['cap'])]['chat']}?param:sessionId={request['id']}"
    try:
        async with http.ws_connect(url) as ws:
            await ws.send_str(json.dumps({
                "value": request["prompt"], "headers": {"ls-trace-id": request["id"]},
            }))
            while True:
                msg = await asyncio.wait_for(ws.receive(), plan["request_timeout_s"])
                now = time.monotonic()
                if msg.type != aiohttp.WSMsgType.TEXT:
                    raise RuntimeError(f"socket closed mid-stream: {msg.type}")
                if note_chunk(out, now, *parse_push(msg.data)):
                    return out
    except (aiohttp.ClientError, asyncio.TimeoutError, RuntimeError) as e:
        out["error"] = f"{type(e).__name__}: {e}"
    return out


async def drive(plan: dict) -> dict:
    import aiohttp

    connector = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=connector) as http:
        results = await asyncio.gather(*(_session(http, plan, r) for r in plan["requests"]))
    return {"requests": list(results)}
