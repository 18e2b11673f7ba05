"""A backlog on the pipeline's input topic, drained as fast as the runtime
admits: every record goes in through the produce gateway at the start, and
the streamed chunks are read from a consume gateway on the step's
`stream-to-topic` and told apart by the trace id each carries. One cap, so
one pipeline. No jax."""

from __future__ import annotations

import asyncio
import json
import time

from .common import new_record, note_chunk, order_rng, parse_push, requests_for


def schedule(params: dict, seed: int, seconds: float, vocab_size: int) -> list[dict]:
    """`backlog_records` fixed in the file, at least twice what the window
    can finish, all due at the start."""
    requests = requests_for(
        params, seed, int(params["backlog_records"]), vocab_size, order_rng(params)
    )
    for request in requests:
        request["due_s"] = 0.0
    return requests


async def drive(plan: dict) -> dict:
    import aiohttp

    caps = {r["cap"] for r in plan["requests"]}
    if len(caps) != 1:
        raise ValueError(f"a drain has one output cap, this one has {sorted(caps)}")
    urls = plan["urls"][str(caps.pop())]
    end = plan["t0"] + plan["seconds"]
    records = {r["id"]: new_record(r, plan["t0"]) for r in plan["requests"]}

    async def consume(ws) -> None:
        # read on past the window's end for nothing: the rate counts what
        # arrived inside it
        while time.monotonic() < end:
            try:
                msg = await asyncio.wait_for(ws.receive(), max(0.05, end - time.monotonic()))
            except asyncio.TimeoutError:
                return
            now = time.monotonic()
            if msg.type != aiohttp.WSMsgType.TEXT:
                raise RuntimeError(f"consume socket closed: {msg.type}")
            headers, text = parse_push(msg.data)
            out = records.get(headers.get("ls-trace-id"))
            if out is not None:  # else the set-up's own probe
                note_chunk(out, now, headers, text)

    async with aiohttp.ClientSession() as http:
        async with http.ws_connect(urls["consume"], max_msg_size=0) as reader:
            await asyncio.sleep(max(0.0, plan["t0"] - time.monotonic()))
            reading = asyncio.create_task(consume(reader))
            async with http.ws_connect(urls["produce"]) as writer:
                for request in plan["requests"]:
                    await writer.send_str(json.dumps({
                        "value": request["prompt"],
                        "headers": {"ls-trace-id": request["id"]},
                    }))
                    ack = json.loads((await writer.receive()).data)
                    if ack.get("status") != "OK":
                        records[request["id"]]["error"] = f"produce refused: {ack}"
                    records[request["id"]]["sent"] = time.monotonic()
            await reading
    return {"requests": list(records.values())}
