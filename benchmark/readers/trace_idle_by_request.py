"""The device's idle time by what the engine held at that instant, from the
window's profile: `class` is `no_request` (nobody had offered work: the
traffic's) or `with_request` (a request was open in the engine while the
device had nothing to do: the engine's), as a share of the profile's window
in percent.

Idle is everything outside the union of the device's "XLA Ops" line inside
the window `reduce/xplane.py` measures, so the classes add up to that run's
`1 - busy_s / window_s`: the gaps between two operations, and the stretch
before the first and after the last operation of the profile, classed like a
gap. A gap under `reduce/xplane.py`'s 20 us floor is launch latency between
operations and is put down to nobody: class `short`. Every other gap is laid
through `reduce/clock.py`'s map against the window's `engine.request` spans
and cut at their edges: a part with no request open is `no_request`, else
`with_request`, and that is split by the state of the OLDEST open request
(its `engine.queued`, `engine.prefill` or `engine.decode` child at that
instant): `with_request.queued` is a device idle while the oldest request
waits for admission, `.prefill` while its first token is on its way, `.decode`
between two of its chunks. Those parts, `short`, `edges` (how much of the
idle lies at the window's two ends), the clock's residual and the
engine's own account over the same seconds (the `unfed_with_request_ms` of the
dispatch spans whose stretch lies in the profile: `account_with_request`)
are printed once as an earlier line, `{"phase": "idle_by_request", ...}`:
evidence for `PERF.md`, never metrics. A profile without `t_mono_ns` on its
launch annotations (the parent's) has no clock and reads as nothing."""

from __future__ import annotations

import json
from typing import Optional

from reduce import clock
from reduce.xplane import MIN_GAP_NS, find_trace

CACHE = "_idle_by_request"
STATES = ("engine.queued", "engine.prefill", "engine.decode")


def idle_intervals(lo: float, hi: float, busy: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """The complement of the merged `busy` intervals inside [lo, hi]."""
    out, edge = [], lo
    for start, end in busy:
        if start > edge:
            out.append((edge, start))
        edge = max(edge, end)
    if hi > edge:
        out.append((edge, hi))
    return out


def requests_on(clock_fit: dict, spans: list[dict]) -> list[dict]:
    """Each request of `spans` as `{"start", "end", "states": [(name, start,
    end)]}` on the profile's clock."""
    def interval(span: dict) -> tuple[float, float]:
        start = clock.to_profile_ns(clock_fit, span["start"])
        return start, start + span["durationMs"] * 1e6

    states: dict[str, list] = {}
    for s in spans:
        if s["name"] in STATES:
            states.setdefault(s["parentId"], []).append((s["name"], *interval(s)))
    return [
        {"start": a, "end": b, "states": states.get(s["spanId"], [])}
        for s in spans if s["name"] == "engine.request"
        for a, b in [interval(s)]
    ]


def split(idle: list[tuple[float, float]], requests: list[dict],
          min_gap_ns: float = MIN_GAP_NS) -> dict[str, float]:
    """ns of `idle` by class: `short`, `no_request`, `with_request` and its
    parts `with_request.<state>` (`with_request.none`: the oldest request is
    between two of its children, by the spans' rounding)."""
    out = {"short": 0.0, "no_request": 0.0, "with_request": 0.0}
    for left, right in idle:
        if right - left < min_gap_ns:
            out["short"] += right - left
            continue
        near = [r for r in requests if r["start"] < right and r["end"] > left]
        cuts = {left, right}
        for r in near:
            for edge in (r["start"], r["end"], *(t for _, a, b in r["states"] for t in (a, b))):
                if left < edge < right:
                    cuts.add(edge)
        ordered = sorted(cuts)
        for a, b in zip(ordered, ordered[1:]):
            mid = (a + b) / 2
            held = [r for r in near if r["start"] <= mid < r["end"]]
            if not held:
                out["no_request"] += b - a
                continue
            oldest = min(held, key=lambda r: r["start"])
            state = next(
                (name for name, s, e in oldest["states"] if s <= mid < e), "engine.none"
            )
            key = "with_request." + state.partition(".")[2]
            out["with_request"] += b - a
            out[key] = out.get(key, 0.0) + b - a
    return out


def account_in_window(clock_fit: dict, spans: list[dict], lo: float, hi: float) -> float:
    """The engine's own with-request unfed ns inside [lo, hi]: each dispatch
    span that closed a stretch says how long the device went unfed before its
    launch and how much of that a request was open; the stretch is clipped to
    the window, its with-request part in proportion."""
    total = 0.0
    for s in spans:
        unfed = s["attributes"].get("unfed_ms")
        if not unfed:
            continue
        end = clock.to_profile_ns(clock_fit, s["start"])
        start = end - unfed * 1e6
        inside = max(0.0, min(end, hi) - max(start, lo))
        total += s["attributes"]["unfed_with_request_ms"] * 1e6 * inside / (end - start)
    return total


def shares(ctx: dict) -> Optional[dict]:
    """Every class as a share of the profile's window (percent), once a run."""
    if CACHE not in ctx:
        ctx[CACHE] = None
        try:
            trace = clock.load(find_trace(ctx["trace_dir"]), **ctx.get("trace_planes", {}))
        except FileNotFoundError:
            return None
        fit = clock.fit(trace["launches"], ctx["spans"])
        if fit is None or not trace["busy"]:
            return None
        lo, hi, planes = trace["lo"], trace["hi"], trace["busy"]
        requests = requests_on(fit, ctx["spans"])
        totals: dict[str, float] = {}
        for busy in planes:  # a program on four chips: the mean over them
            for key, ns in split(idle_intervals(lo, hi, busy), requests).items():
                totals[key] = totals.get(key, 0.0) + ns / len(planes)
        out = {key: 100.0 * ns / (hi - lo) for key, ns in totals.items()}
        # the two ends, already classed above: how much of the idle lies before
        # the first and after the last operation of the profile
        out["edges"] = 100.0 * sum(
            (busy[0][0] - lo) + (hi - busy[-1][1]) for busy in planes
        ) / len(planes) / (hi - lo)
        out["account_with_request"] = (
            100.0 * account_in_window(fit, ctx["spans"], lo, hi) / (hi - lo)
        )
        ctx[CACHE] = out
        print(json.dumps({
            "phase": "idle_by_request", **{k: round(v, 4) for k, v in out.items()},
            "window_s": (hi - lo) / 1e9, "clock_residual_ms": fit["residual_ns"] / 1e6,
            "clock_launches": fit["launches"],
        }), flush=True)
    return ctx[CACHE]


def read(definition: dict, ctx: dict) -> Optional[float]:
    if not ctx.get("trace_dir"):
        return None
    out = shares(ctx)
    return out.get(definition["class"]) if out else None
