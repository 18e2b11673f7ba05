"""What a first token waited for, one stage at a time: the mean over the
window's requests of one `stage` of the client's time to first token, in ms.

The engine's five: `queued` (the request's `engine.queued` span: submitted →
admitted) and the four attributes its `engine.prefill` span copied from the
dispatch that brought its first token when that landed: `launch` (admitted →
the dispatch's launch: the host building it), `behind` (launch → the later
of launch and the previous result's ready: what was in flight ahead),
`device` (the dispatch's own device time) and `land` (the result on the host →
the engine thread stamping the first token). By construction the five add up
to the request's submitted → first token. `outside` is the client's time to
first token (from DUE) minus those two spans, joined on the trace id the client
chose as `client_minus_spans` joins: gateway, broker, agent runtime and the
way back. So the six means add up to `ttft_mean_ms` of the same run, over
the requests that have a first chunk and their spans. A program whose
`engine.prefill` carries no such attribute (the parent of the PR that brought
them) reads as nothing."""

from __future__ import annotations

from typing import Optional

from metrics import ttft_ms


def read(definition: dict, ctx: dict) -> Optional[float]:
    stage = definition["stage"]
    queued, prefill = {}, {}
    for span in ctx["spans"]:
        if span["name"] == "engine.queued":
            queued[span["traceId"]] = span
        elif span["name"] == "engine.prefill":
            prefill[span["traceId"]] = span
    values = []
    for r in ctx["requests"]:
        if r.get("t_first") is None or r["id"] not in queued or r["id"] not in prefill:
            continue
        if stage == "queued":
            values.append(queued[r["id"]]["durationMs"])
        elif stage == "outside":
            values.append(
                ttft_ms(r) - queued[r["id"]]["durationMs"] - prefill[r["id"]]["durationMs"]
            )
        elif (value := prefill[r["id"]]["attributes"].get(f"{stage}_ms")) is not None:
            values.append(value)
    return sum(values) / len(values) if values else None
